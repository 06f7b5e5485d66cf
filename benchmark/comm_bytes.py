"""Bytes that a data- and tensor-parallel training step must move between
chips, computed from the configuration alone.  This is the yardstick of
the step's communication work, as ``flops.py`` is of its arithmetic: the
program is never asked how much it moved, so the count reads the same
work whatever later implements the step.

The step is Megatron's: on a ``dp`` x ``tp`` mesh the fused QKV, gate and
up projections are column-parallel and the attention output and down
projections row-parallel over ``tp``, each head's query, key and value on
the chip that holds the head; the tied embedding is sharded over the
vocabulary; every gradient is summed over ``dp``.  Activations and the
gradients of the weights' compute-dtype copies are in the compute dtype
(``itemsize``); master weights stay on their chips.

Bus bytes per chip follow nccl-tests, as ``flops.bus_bytes`` does: an
all-reduce over ``n`` chips moves 2(n-1)/n of its buffer.
"""

from __future__ import annotations

F32 = 4


def _allreduce(payload: float, n: int) -> float:
    return 2.0 * (n - 1) / n * payload if n > 1 else 0.0


def tp_payload(d_model: int, n_layers: int, rows: int, seq: int,
               itemsize: int = 2) -> int:
    """Bytes each chip all-reduces over ``tp`` in one step, ``rows``
    being the rows of the batch on its ``dp`` replica:

    - per layer, the row-parallel outputs of the attention and the MLP
      in the forward, and the gradients of the column-parallel inputs
      (the normed residual before QKV, and before gate and up) in the
      backward: four (rows, seq, d_model) buffers;
    - the vocabulary-sharded embedding lookup (forward) and the tied
      head's input gradient (backward): two more;
    - the vocabulary-sharded softmax's per-position statistics: the
      largest logit in the compute dtype (a maximum is exact in any
      dtype) and, in float32, the sum of exponentials and the target's
      logit."""
    acts = (4 * n_layers + 2) * rows * seq * d_model * itemsize
    stats = rows * seq * (itemsize + 2 * F32)
    return acts + stats


def dp_payload(d_model: int, n_layers: int, n_heads: int, head_dim: int,
               d_ff: int, vocab: int, tp: int, itemsize: int = 2) -> int:
    """Bytes each chip all-reduces over ``dp`` in one step: the gradient
    of every weight it holds, once, in the compute dtype.  Matrices and
    the embedding are split over ``tp``; the norm scales are whole on
    every chip."""
    h = n_heads * head_dim
    split = (n_layers * (d_model * 3 * h + h * d_model + 3 * d_model * d_ff)
             + vocab * d_model)
    whole = (2 * n_layers + 1) * d_model
    return (split // tp + whole) * itemsize


def step_bus_bytes(cfg: dict, rows_per_replica: int, seq: int, dp: int,
                   tp: int, itemsize: int = 2) -> dict:
    """{"tp": bus bytes, "dp": bus bytes} per chip per step, for a
    configuration file's keys."""
    tp_b = tp_payload(cfg["d_model"], cfg["n_layers"], rows_per_replica,
                      seq, itemsize)
    dp_b = dp_payload(cfg["d_model"], cfg["n_layers"], cfg["n_heads"],
                      cfg["head_dim"], cfg["mlp_hidden_size"],
                      cfg["embedding_size"], tp, itemsize)
    return {"tp": _allreduce(tp_b, tp), "dp": _allreduce(dp_b, dp)}
