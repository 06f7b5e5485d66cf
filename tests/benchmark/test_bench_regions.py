"""The readers of the program's region table (benchmark/regions.py) on a
planted table: each reads its number, and reads nothing where the table
holds none of its regions, where the run traced no window, or where the
program keeps no table."""

import os

import pytest

from benchmark import run as br
from ompi_tpu import trace

TRACED = {"records": {}, "reduced": {}, "cell": {},
          "device_kind": "TPU v5 lite",
          "trace": {"devices": {"/device:TPU:0": [(0, 1, "%a = f32[] a()")]},
                    "modules": {}, "spans": []}}


def _row(count, total, own=None):
    return {"count": count, "total_s": total,
            "self_s": total if own is None else own}


PLANTED = {
    # four collective calls: 1.5 ms of their own, 0.4 + 0.8 + 2.3 ms in
    # the three parts; a build inside a launch is no call of its own
    "ompi.coll.allreduce": _row(3, 3.0e-3, 1.0e-3),
    "ompi.coll.alltoallv": _row(1, 2.0e-3, 0.5e-3),
    "ompi.coll.decide": _row(4, 0.4e-3),
    "ompi.coll.audit": _row(4, 0.8e-3),
    "ompi.coll.launch": _row(4, 2.3e-3),
    "ompi.coll.build": _row(1, 0.1e-3),
    # ten decode steps and two prefills
    "ompi.serve.step": _row(10, 700e-3, 20e-3),
    "ompi.serve.admit": _row(2, 80e-3, 4e-3),
    "ompi.engine.decode": _row(10, 680e-3, 0.0),
    "ompi.engine.decode.dispatch": _row(10, 300e-3),
    "ompi.engine.prefill.dispatch": _row(2, 50e-3),
    "ompi.compile": _row(2, 1.5),
}

EXPECTED = [
    ("coll_hooks_us", 375.0), ("coll_decide_us", 100.0),
    ("coll_audit_us", 200.0), ("coll_launch_us", 575.0),
    ("serve_decode_dispatch_ms", 30.0), ("serve_prefill_dispatch_ms", 25.0),
    ("serve_sched_ms", 2.4),
    ("window_compiles.osu", 2), ("window_compiles.train", 2),
    ("window_compiles.serve", 2),
]
NAMES = [n for n, _ in EXPECTED]


def _reader(name):
    return br.load_module(os.path.join(br.BENCH_DIR, "metrics",
                                       f"{name}.py"),
                          "bench_metric_" + name.replace(".", "_"))


def _plant(monkeypatch, table):
    monkeypatch.setattr(trace, "regions", lambda: {
        n: dict(r) for n, r in table.items()})


@pytest.mark.parametrize("name,want", EXPECTED)
def test_reader_on_planted_table(monkeypatch, name, want):
    _plant(monkeypatch, PLANTED)
    assert _reader(name).read(TRACED) == pytest.approx(want, rel=1e-9)


def test_coll_parts_sum_to_the_calls_dispatch(monkeypatch):
    _plant(monkeypatch, PLANTED)
    got = sum(_reader(f"coll_{p}_us").read(TRACED)
              for p in ("hooks", "decide", "audit", "launch"))
    calls = PLANTED["ompi.coll.allreduce"]["count"] + 1
    total = (PLANTED["ompi.coll.allreduce"]["total_s"]
             + PLANTED["ompi.coll.alltoallv"]["total_s"])
    assert got == pytest.approx(total / calls * 1e6)


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_empty_table(monkeypatch, name):
    _plant(monkeypatch, {})
    # no region closed in the window: no time to read; no compile is a 0
    want = 0 if name.startswith("window_compiles.") else None
    assert _reader(name).read(TRACED) == want


@pytest.mark.parametrize("name", NAMES)
def test_reader_without_a_traced_window_or_a_table(monkeypatch, name):
    _plant(monkeypatch, PLANTED)
    untraced = dict(TRACED, trace={"devices": {}, "spans": []})
    assert _reader(name).read(untraced) is None
    monkeypatch.delattr(trace, "regions")      # a program without it
    assert _reader(name).read(TRACED) is None
