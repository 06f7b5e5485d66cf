"""ops — Pallas/XLA kernels for the hot paths.

The reference's only hand-tuned compute is the AVX reduction kernels
(ompi/mca/op/avx/op_avx_component.c:45-47) — on TPU the analogous "do the
math where the data is" components are Pallas kernels:

  * ``attention`` — block flash attention (VMEM-resident online softmax),
    plus a partials variant that plugs into ring attention's merge step;
  * ``paged_attention`` — decode attention that reads each row's live
    pages of the serving tier's KV pools in place, by block table;
  * ``collective_matmul`` — latency-hiding allgather-matmul and
    matmul-reduce-scatter (comm/compute overlap on ICI), the TPU-native
    answer to the reference's segmented/pipelined collectives
    (coll_base_allreduce.c:344,621).
"""

from .attention import (flash_attention, flash_attention_partials,  # noqa: F401
                        flash_mha)
from .collective_matmul import allgather_matmul, matmul_reduce_scatter  # noqa: F401
