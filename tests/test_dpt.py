"""DPT Algorithm 1 semantics + beyond-paper search strategies."""
import math

import pytest
from _hypothesis_shim import given, settings, st

from repro.core import (DPT, DPTConfig, LoaderSimulator, MachineProfile,
                        MemoryOverflow, MultiHostDPT, SimulatorEvaluator,
                        default_params)
from repro.core.cache import DPTCache
from repro.core.cluster import fleet_evaluators, make_fleet
from repro.data.loader import TransferStats
from repro.data.storage import StorageProfile, cifar10_profile
from repro.tuning import cost_model_warmstart, tune


class TableEvaluator:
    """Deterministic synthetic objective with optional overflow cells."""

    def __init__(self, fn, overflow=None):
        self.fn = fn
        self.overflow = overflow or (lambda i, j: False)
        self.calls = []

    def __call__(self, i, j, *, num_batches=16, epoch=0):
        self.calls.append((i, j))
        if self.overflow(i, j):
            raise MemoryOverflow(f"cell ({i},{j})")
        return TransferStats(self.fn(i, j), num_batches, 0)


def test_algorithm1_visits_worker_multiples_of_G():
    ev = TableEvaluator(lambda i, j: abs(i - 8) + 0.1 * abs(j - 3))
    cfg = DPTConfig(num_cpu_cores=12, num_devices=4, max_prefetch=4,
                    num_batches=4)
    res = DPT(ev, cfg).run(measure_default=False)
    workers = {i for i, _ in ev.calls}
    assert workers == {4, 8, 12}          # G, 2G, 3G (i > N stops)
    assert res.nworker == 8 and res.nprefetch == 3


def test_algorithm1_finds_grid_argmin():
    fn = lambda i, j: (i - 6) ** 2 + (j - 2) ** 2 + 1.0
    ev = TableEvaluator(fn)
    cfg = DPTConfig(num_cpu_cores=12, num_devices=1, max_prefetch=6,
                    num_batches=4)
    res = DPT(ev, cfg).run(measure_default=False)
    assert (res.nworker, res.nprefetch) == (6, 2)
    assert res.optimal_time == 1.0


def test_memory_overflow_breaks_inner_loop():
    """Paper Algorithm 1 lines 9-10: overflow -> break to next worker count."""
    ev = TableEvaluator(lambda i, j: 10.0 - i + 0.1 * j,
                        overflow=lambda i, j: j >= 3)
    cfg = DPTConfig(num_cpu_cores=4, num_devices=1, max_prefetch=8,
                    num_batches=4)
    res = DPT(ev, cfg).run(measure_default=False)
    # for every worker count, j stops at 3 (first overflow)
    for i in range(1, 5):
        js = [j for (w, j) in ev.calls if w == i]
        assert js == [1, 2, 3]
    assert res.nprefetch <= 2


def test_default_params_match_pytorch_convention():
    assert default_params(12) == (6, 2)


def test_speedup_and_reduction_sign():
    """An improvement over the defaults is a POSITIVE time reduction."""
    ev = TableEvaluator(lambda i, j: 2.0 if (i, j) != (4, 2) else 1.0)
    cfg = DPTConfig(num_cpu_cores=4, num_devices=4, max_prefetch=2,
                    num_batches=4)
    res = DPT(ev, cfg).run(measure_default=True)
    assert res.speedup_vs_default == 2.0
    assert res.time_reduction_pct == pytest.approx(50.0)


def test_worker_sweep_clamps_final_rung_to_cores():
    """N not divisible by G must not measure more workers than cores."""
    ev = TableEvaluator(lambda i, j: float(i + j))
    cfg = DPTConfig(num_cpu_cores=10, num_devices=4, max_prefetch=2,
                    num_batches=4)
    DPT(ev, cfg).run(measure_default=False)
    workers = {i for i, _ in ev.calls}
    assert workers == {4, 8, 10}          # last rung clamped, not 12


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4), st.integers(2, 16), st.integers(1, 6))
def test_algorithm1_never_beats_exhaustive_property(g, n, p):
    """Property: Algorithm 1's optimum equals the exhaustive grid minimum
    over its own search space."""
    fn = lambda i, j: ((i * 7 + j * 13) % 11) + 1.0
    ev = TableEvaluator(fn)
    cfg = DPTConfig(num_cpu_cores=n, num_devices=g, max_prefetch=p,
                    num_batches=2)
    res = DPT(ev, cfg).run(measure_default=False)
    # mirror Algorithm 1's loop exactly (final rung clamped to N)
    i_vals, i = [], 0
    while i < n:
        i = min(i + g, n)
        i_vals.append(i)
    cells = [(i, j) for i in i_vals for j in range(1, p + 1)]
    assert res.optimal_time == min(fn(i, j) for i, j in cells)


# --------------------------------------------------------------------------
# search strategies agree with the grid on the calibrated simulator
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def sim_ev():
    sim = LoaderSimulator(cifar10_profile(), MachineProfile())
    return SimulatorEvaluator(sim, batch_size=32)


CFG = DPTConfig(num_cpu_cores=12, num_devices=1, max_prefetch=8,
                num_batches=64)


def test_successive_halving_matches_grid(sim_ev):
    grid = DPT(sim_ev, CFG).run(measure_default=False)
    sh = tune(evaluator=sim_ev, strategy="successive_halving", config=CFG)
    assert sh.optimal_time <= grid.optimal_time * 1.05


def test_warmstart_hillclimb_matches_grid_with_fewer_calls(sim_ev):
    grid = DPT(sim_ev, CFG).run(measure_default=False)
    ev2 = SimulatorEvaluator(LoaderSimulator(cifar10_profile(),
                                             MachineProfile()), batch_size=32)
    hc = tune(evaluator=ev2, strategy="warmstart_hillclimb", config=CFG,
              storage=cifar10_profile(), machine=MachineProfile(),
              batch_size=32)
    assert hc.optimal_time <= grid.optimal_time * 1.02
    assert ev2.calls < len(grid.trials) / 4      # >=4x fewer measurements


def test_goodput_uses_fewer_workers_when_model_is_slow(sim_ev):
    fast = DPT(sim_ev, CFG).run(measure_default=False)
    slow_model = tune(evaluator=sim_ev, strategy="goodput", config=CFG,
                      step_time_s=1.0, num_batches=64)
    assert slow_model.nworker <= fast.nworker


def test_cost_model_prediction_close_to_measured_optimum(sim_ev):
    pred = cost_model_warmstart(cifar10_profile(), MachineProfile(),
                                batch_size=32, config=CFG)
    grid = DPT(sim_ev, CFG).run(measure_default=False)
    assert abs(pred.nworker - grid.nworker) <= 2


# --------------------------------------------------------------------------
# multi-host
# --------------------------------------------------------------------------
def test_multihost_uniform_handles_straggler():
    fleet = make_fleet(MachineProfile(), cifar10_profile(), num_hosts=4,
                       slow_hosts=[1])
    evs = fleet_evaluators(fleet, batch_size=32)
    mh = MultiHostDPT(evs, CFG)
    per_host = mh.run_per_host()
    uniform = mh.run_uniform()
    # fleet time is dictated by the straggler either way
    assert uniform.fleet_time >= per_host.per_host[0].optimal_time
    # uniform must be feasible on every host and not much worse than per-host
    assert uniform.fleet_time <= per_host.fleet_time * 1.05


def test_multihost_per_host_matches_independent_tuning():
    fleet = make_fleet(MachineProfile(), cifar10_profile(), num_hosts=3)
    evs = fleet_evaluators(fleet, batch_size=32)
    res = MultiHostDPT(evs, CFG).run_per_host()
    assert len(set(res.fleet_params)) == 1   # homogeneous hosts agree


# ---- run_uniform edge cases ----------------------------------------------
_EDGE_CFG = DPTConfig(num_cpu_cores=2, num_devices=1, max_prefetch=2,
                      num_batches=2)


def test_multihost_uniform_single_feasible_cell():
    """When only one cell survives on every host, uniform must pick it."""
    only = (1, 1)
    evs = [TableEvaluator(lambda i, j: float(i + j),
                          overflow=lambda i, j: (i, j) != only)
           for _ in range(3)]
    res = MultiHostDPT(evs, _EDGE_CFG).run_uniform()
    assert res.uniform_params == only
    assert res.fleet_params == [only] * 3


def test_multihost_uniform_no_common_feasible_cell_raises():
    """Host A only feasible at i=1, host B only at i=2 -> no uniform cell."""
    ev_a = TableEvaluator(lambda i, j: 1.0, overflow=lambda i, j: i > 1)
    ev_b = TableEvaluator(lambda i, j: 1.0, overflow=lambda i, j: i == 1)
    with pytest.raises(MemoryOverflow):
        MultiHostDPT([ev_a, ev_b], _EDGE_CFG).run_uniform()


def test_multihost_uniform_straggler_picks_max_minimizing_cell():
    """The uniform choice minimizes the fleet MAX, not any host's own
    optimum: host A loves (1,1) but the straggler B is terrible there."""
    ev_a = TableEvaluator(lambda i, j: 1.0 if (i, j) == (1, 1) else 2.0)
    ev_b = TableEvaluator(lambda i, j: 10.0 if (i, j) == (1, 1) else 2.0)
    res = MultiHostDPT([ev_a, ev_b], _EDGE_CFG).run_uniform()
    assert res.uniform_params != (1, 1)
    assert res.fleet_time == 2.0


# --------------------------------------------------------------------------
# result cache (paper §5 reuse claim)
# --------------------------------------------------------------------------
def test_cache_reuses_similar_datasets(tmp_path):
    cache = DPTCache(str(tmp_path / "dpt.json"))
    ev = TableEvaluator(lambda i, j: (i - 6) ** 2 + j)
    cfg = DPTConfig(num_cpu_cores=8, num_devices=1, max_prefetch=3,
                    num_batches=2)
    res = DPT(ev, cfg).run(measure_default=False)
    from repro.utils.fingerprint import dataset_fingerprint
    fp_a = dataset_fingerprint(item_bytes=100_000, decode_cost=1e-8,
                               num_items=50_000)
    fp_similar = dataset_fingerprint(item_bytes=110_000, decode_cost=1e-8,
                                     num_items=52_000)
    fp_different = dataset_fingerprint(item_bytes=4_000_000, decode_cost=1e-8,
                                       num_items=50_000)
    cache.put("machine", fp_a, 32, res)
    assert cache.get_params("machine", fp_similar, 32) == \
        (res.nworker, res.nprefetch, {})
    assert cache.get_params("machine", fp_different, 32) is None
    # persisted
    cache2 = DPTCache(str(tmp_path / "dpt.json"))
    assert cache2.get_params("machine", fp_a, 32) == \
        (res.nworker, res.nprefetch, {})


def test_machine_fingerprint_separates_device_kinds():
    """A loader pick tuned on one accelerator is never reused on another:
    the machine fingerprint includes the platform and device kind."""
    from repro.utils.fingerprint import machine_fingerprint
    host = dict(cpu_count=8, device_count=1, host_ram_bytes=64 << 30)
    cpu = machine_fingerprint(platform_name="cpu", device_kind="cpu", **host)
    tpu = machine_fingerprint(platform_name="tpu", device_kind="TPU v5 lite",
                              **host)
    assert cpu != tpu
    assert cpu == machine_fingerprint(platform_name="cpu", device_kind="cpu",
                                      **host)
    # the defaults describe this process's own first device
    import jax
    dev = jax.local_devices()[0]
    assert machine_fingerprint(**host) == machine_fingerprint(
        platform_name=dev.platform, device_kind=dev.device_kind, **host)
