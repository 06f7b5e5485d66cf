"""Core substrate tests (≙ reference test/util + MCA var behavior)."""

import os

import pytest

from ompi_tpu.core import var
from ompi_tpu.core.component import Component, component, frameworks
from ompi_tpu.core.progress import ProgressEngine
from ompi_tpu.core.var import VarSource


def test_var_default():
    v = var.register("testfw", "compA", "knob", 42, help="a knob")
    assert v.value == 42
    assert v.source == VarSource.DEFAULT
    assert var.get("testfw_compA_knob") == 42


def test_var_env_overrides_default(monkeypatch):
    monkeypatch.setenv("OMPI_TPU_testfw_compB_knob", "7")
    v = var.register("testfw", "compB", "knob", 1)
    assert v.value == 7
    assert v.source == VarSource.ENV


def test_var_cli_overrides_env(monkeypatch):
    monkeypatch.setenv("OMPI_TPU_testfw_compC_knob", "7")
    var.registry.set_cli("testfw_compC_knob", "9")
    v = var.register("testfw", "compC", "knob", 1)
    assert v.value == 9
    assert v.source == VarSource.CLI


def test_var_override_highest():
    var.register("testfw", "compD", "knob", 1)
    var.registry.set_override("testfw_compD_knob", 123)
    assert var.get("testfw_compD_knob") == 123


def test_var_file_source(tmp_path, monkeypatch):
    f = tmp_path / "params.conf"
    f.write_text("# comment\ntestfw_compE_knob = 55\n")
    monkeypatch.setenv("OMPI_TPU_PARAMS_FILE", str(f))
    var.registry.reset_cache()
    v = var.register("testfw", "compE", "knob", 1)
    assert v.value == 55
    assert v.source == VarSource.FILE


def test_var_bool_conversion(monkeypatch):
    monkeypatch.setenv("OMPI_TPU_testfw_compF_flag", "true")
    v = var.register("testfw", "compF", "flag", False)
    assert v.value is True


def test_component_priority_selection():
    @component("tfw1", "low", priority=10)
    class Low(Component):
        def query(self, scope):
            return self.priority, "low-module"

    @component("tfw1", "high", priority=50)
    class High(Component):
        def query(self, scope):
            return self.priority, "high-module"

    comp, module = frameworks.framework("tfw1").select()
    assert comp.name == "high"
    assert module == "high-module"


def test_component_exclude_list():
    @component("tfw2", "a", priority=50)
    class A(Component):
        def query(self, scope):
            return self.priority, "a"

    @component("tfw2", "b", priority=10)
    class B(Component):
        def query(self, scope):
            return self.priority, "b"

    var.registry.set_cli("tfw2_select", "^a")
    var.register("tfw2", "", "select", "")
    var.registry.reset_cache()
    comp, _ = frameworks.framework("tfw2").select()
    assert comp.name == "b"
    var.registry.set_cli("tfw2_select", "")
    var.registry.reset_cache()


def test_component_decline():
    @component("tfw3", "declines", priority=100)
    class D(Component):
        def query(self, scope):
            return None, None

    @component("tfw3", "accepts", priority=1)
    class Acc(Component):
        def query(self, scope):
            return self.priority, "ok"

    comp, module = frameworks.framework("tfw3").select()
    assert comp.name == "accepts"


def test_component_select_all_ordering():
    @component("tfw4", "x", priority=5)
    class X(Component):
        def query(self, scope):
            return self.priority, None

    @component("tfw4", "y", priority=20)
    class Y(Component):
        def query(self, scope):
            return self.priority, None

    rows = frameworks.framework("tfw4").select_all()
    assert [r[1].name for r in rows] == ["y", "x"]


def test_progress_engine_completion():
    eng = ProgressEngine()
    state = {"n": 0}

    def cb():
        state["n"] += 1
        return 1

    eng.register(cb)
    assert eng.wait_until(lambda: state["n"] >= 5, timeout=1.0)
    assert state["n"] >= 5


def test_progress_low_priority_runs_less():
    eng = ProgressEngine()
    hi, lo = {"n": 0}, {"n": 0}
    eng.register(lambda: hi.update(n=hi["n"] + 1) or 0)
    eng.register(lambda: lo.update(n=lo["n"] + 1) or 0, low_priority=True)
    for _ in range(64):
        eng.progress()
    assert hi["n"] == 64
    assert lo["n"] == 8


def test_show_help_dedup(capsys):
    from ompi_tpu.core.output import ShowHelp
    sh = ShowHelp()
    sh.show("no-component", "coll", "coll_select", "")
    sh.show("no-component", "coll", "coll_select", "")
    err = capsys.readouterr().err
    assert err.count("No usable component") == 1


# ---------------------------------------------------------------------------
# hwloc-lite host topology + binding (core/hwtopo.py ≙ opal/mca/hwloc + the
# PRRTE binding role, SURVEY.md §2.2 row 24 / §3.4)
# ---------------------------------------------------------------------------

def test_hwtopo_discovery_and_plan():
    from ompi_tpu.core import hwtopo
    mach = hwtopo.topology(refresh=True)
    assert mach.n_pus >= 1
    assert mach.n_cores >= 1
    assert len(mach.packages) >= 1
    assert "machine:" in mach.summary()
    # every PU appears exactly once in the tree
    pus = [pu for p in mach.packages for c in p.cores for pu in c.pus]
    assert len(pus) == len(set(pus))
    for n in (1, 2, 5):
        plan = hwtopo.bind_plan(n, "core")
        assert len(plan) == n and all(cs for cs in plan)
        plan = hwtopo.bind_plan(n, "package")
        assert len(plan) == n and all(cs for cs in plan)
    assert hwtopo.bind_plan(3, "none") == [[], [], []]


def test_hwtopo_cpulist_and_env_binding():
    from ompi_tpu.core import hwtopo
    assert hwtopo._parse_cpulist("0-3,8,10-11") == [0, 1, 2, 3, 8, 10, 11]
    assert hwtopo.apply_env_binding({}) is None
    import os
    mine = sorted(os.sched_getaffinity(0))
    got = hwtopo.apply_env_binding(
        {"OMPI_TPU_BIND_CPUS": ",".join(map(str, mine))})
    assert got == mine


def test_launcher_bind_env():
    from ompi_tpu.control.launch import build_env
    env = build_env({}, rank=0, size=2, coord="h:1", job="j", mca=[],
                    bind_to="core")
    assert "OMPI_TPU_BIND_CPUS" in env
    env2 = build_env({}, rank=0, size=2, coord="h:1", job="j", mca=[])
    assert "OMPI_TPU_BIND_CPUS" not in env2


@pytest.mark.parametrize("chips,procs,per", [(1, "2,2,1", "1,1,1"),
                                              (2, "1,2,1", "2,1,1")])
def test_launcher_chip_env(chips, procs, per):
    """--chips-per-rank hands each rank libtpu's multi-process contract:
    its own chips, the bounds, and every local process's address."""
    from ompi_tpu.control.launch import build_env
    n = 4 // chips
    envs = [build_env({}, rank=r, size=n, coord="h:1", job="j", mca=[],
                      chips_per_rank=chips) for r in range(n)]
    owned = [e["TPU_VISIBLE_CHIPS"].split(",") for e in envs]
    assert sorted(sum(owned, [])) == ["0", "1", "2", "3"]
    assert {e["TPU_PROCESS_BOUNDS"] for e in envs} == {procs}
    assert {e["TPU_CHIPS_PER_PROCESS_BOUNDS"] for e in envs} == {per}
    addrs = envs[0]["TPU_PROCESS_ADDRESSES"].split(",")
    assert len(addrs) == n
    assert [f"localhost:{e['TPU_PROCESS_PORT']}" for e in envs] == addrs
    assert [e["CLOUD_TPU_TASK_ID"] for e in envs] == [str(r)
                                                      for r in range(n)]


def test_interlib_declare_query_withdraw():
    """interlib (≙ ompi/interlib/interlib.c): co-resident runtimes declare
    themselves; the effective thread level is the most restrictive; query
    reports whether an ompi_tpu Context is live."""
    from ompi_tpu import runtime

    runtime.interlib_declare("serving-stack", "1.2",
                             runtime.THREAD_MULTIPLE)
    runtime.interlib_declare("legacy-lib", "0.9",
                             runtime.THREAD_FUNNELED)
    q = runtime.interlib_query()
    assert set(q["libraries"]) >= {"serving-stack", "legacy-lib"}
    assert q["thread_level"] == runtime.THREAD_FUNNELED

    def fn(ctx):
        inner = runtime.interlib_query()
        # a live Context (run_ranks-created, not just init()'s singleton)
        # must report the runtime active — the collision interlib prevents
        assert inner["runtime_active"] is True
        return inner["libraries"]["serving-stack"]["version"]

    assert runtime.run_ranks(1, fn) == ["1.2"]
    assert runtime.interlib_withdraw("legacy-lib")
    assert not runtime.interlib_withdraw("legacy-lib")
    assert runtime.interlib_query()["thread_level"] == \
        runtime.THREAD_MULTIPLE
    runtime.interlib_withdraw("serving-stack")
