import json
import os
import sys

# src layout import path (tests also run without `pip install -e .`)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Correctness suite: fp32 compute for deterministic comparisons.  Must be
# set before any repro.models import.  (The dry-run/benchmarks use bf16.)
os.environ.setdefault("REPRO_COMPUTE_DTYPE", "float32")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


# --------------------------------------------------------------------------
# shared pipeline scaffolding (deduped from test_fastpath / test_locality /
# test_fleet, which each used to re-implement these builders)
# --------------------------------------------------------------------------
def make_index_dataset(n, *, width=4, transform=None):
    """Dataset whose sample VALUES are their indices — delivered batches
    can be audited for exact coverage (see ``flat_indices``).  A custom
    ``transform`` (fault injection, skewed per-batch cost, ...) receives
    the raw ``(width,)`` index array."""
    from repro.data import Dataset
    from repro.data.storage import ArrayStorage
    items = [np.full((width,), i, np.int32) for i in range(n)]
    return Dataset(ArrayStorage(items),
                   transform=transform or (lambda a: {"x": a}))


def flat_indices(batches):
    """Sorted sample indices recovered from index-dataset batches."""
    return sorted(np.concatenate(
        [np.asarray(b["x"])[:, 0] for b in batches]).tolist())


def make_cold_dataset(n, *, latency_s=1e-3, cache_bytes=0, bandwidth=1e9,
                      item_shape=(8, 8, 3), tail_fraction=0.0,
                      tail_mult=1.0, tail_seed=0, tail_mode="bimodal",
                      fault_rate=0.0, fault_seed=0, brownout=None):
    """Seek-bound cold storage: every miss pays a base latency, which is
    what makes coalesced (chunked-order) reads measurably faster.  The
    tail knobs plant deterministic stragglers (DESIGN.md §9): a seeded
    ``tail_fraction`` of items costs ``tail_mult``x extra on every miss.
    The fault knobs (DESIGN.md §10) inject seeded transient read errors
    and a timed brownout window on the same splitmix64 hashing."""
    from repro.data import ArrayStorage, Dataset, LatencyStorage
    from repro.data.dataset import image_transform
    rng = np.random.default_rng(0)
    items = [rng.integers(0, 255, item_shape, dtype=np.uint8)
             for _ in range(n)]
    storage = LatencyStorage(ArrayStorage(items), latency_s=latency_s,
                             bandwidth=bandwidth, cache_bytes=cache_bytes,
                             tail_fraction=tail_fraction,
                             tail_mult=tail_mult, tail_seed=tail_seed,
                             tail_mode=tail_mode, fault_rate=fault_rate,
                             fault_seed=fault_seed, brownout=brownout)
    return Dataset(storage, transform=image_transform)


def make_table_evaluator(fn, *, locality=False, cache=False):
    """Synthetic evaluator over a (nworker, nprefetch[, chunk]) table;
    records call count and per-call budgets like the real ones.  The
    ``cache`` variant takes the full 4-axis cell plus the epoch —
    ``fn(i, j, chunk, budget, epoch)`` — so tests can price the cache
    axis warm vs cold."""
    from repro.data.loader import TransferStats

    if cache:
        def ev(i, j, *, num_batches=16, epoch=0, locality_chunk=None,
               cache_budget_bytes=None):
            ev.calls += 1
            ev.budgets.append(num_batches)
            ev.epochs.append(epoch)
            return TransferStats(fn(i, j, locality_chunk or 0,
                                    cache_budget_bytes or 0, epoch),
                                 num_batches, 0)
    elif locality:
        def ev(i, j, *, num_batches=16, epoch=0, locality_chunk=None):
            ev.calls += 1
            ev.budgets.append(num_batches)
            return TransferStats(fn(i, j, locality_chunk or 0),
                                 num_batches, 0)
    else:
        def ev(i, j, *, num_batches=16, epoch=0):
            ev.calls += 1
            ev.budgets.append(num_batches)
            return TransferStats(fn(i, j), num_batches, 0)
    ev.calls = 0
    ev.budgets = []
    ev.epochs = []
    return ev


@pytest.fixture
def index_dataset():
    return make_index_dataset


@pytest.fixture
def cold_dataset():
    return make_cold_dataset


@pytest.fixture
def table_evaluator():
    return make_table_evaluator


class FleetHarness:
    """A live in-process fleet: coordinator + one HostAgent/loader/stream
    per host, driven by a fake clock.  Streams the factory handed out are
    closed at teardown even when a test bails early."""

    def __init__(self, coord, agents, streams, clock):
        self.coord = coord
        self.agents = agents
        self.streams = streams
        self.clock = clock

    def tick(self, dt=1.0):
        self.clock[0] += dt

    def close(self):
        for s in self.streams:
            try:
                s.close()
            except Exception:
                pass


@pytest.fixture
def fleet_factory():
    """Factory for a live fleet harness (see ``FleetHarness``)."""
    from repro.data import DataLoader, LoaderParams
    from repro.tuning import FleetConfig, FleetCoordinator, HostAgent

    harnesses = []

    def build(n=480, gb=12, hosts=3, *, timeout=5.0, seed=5,
              evaluator_fn=lambda i, j: 4.0 / i + 0.1 * j,
              config=None, **cfg_kw):
        clock = [0.0]
        defaults = dict(heartbeat_timeout_s=timeout, warmup_steps=2,
                        cooldown_steps=4, num_cpu_cores=4, num_devices=1,
                        max_prefetch=2, retune_budget_batches=2)
        defaults.update(cfg_kw)
        cfg = config or FleetConfig(**defaults)
        coord = FleetCoordinator(config=cfg, clock=lambda: clock[0])
        agents, streams = [], []
        for h in range(hosts):
            dl = DataLoader(make_index_dataset(n), gb, shuffle=True,
                            seed=seed,
                            params=LoaderParams(num_workers=2,
                                                prefetch_factor=2),
                            host_index=h, host_count=hosts)
            agent = coord.register(HostAgent(
                f"host{h}", dl,
                evaluator=make_table_evaluator(evaluator_fn)))
            agents.append(agent)
            streams.append(dl.stream(to_device=False))
        harness = FleetHarness(coord, agents, streams, clock)
        harnesses.append(harness)
        return harness

    yield build
    for h in harnesses:
        h.close()


class WireFleet:
    """A transport-mode fleet (DESIGN.md §8): hosts talk to the
    coordinator over a (fault-injectable) message transport, a lease +
    snapshot store back a standby replica, and a fake clock drives
    heartbeats, lease expiry, and failover deterministically.

    ``rounds`` is one lockstep driver step: every alive host pulls one
    batch and observes (reports cross the wire or park in the link's
    bounded queue), delayed messages pump, the leader ticks its lease and
    polls, and the standby watches for expiry — promotion swaps
    ``self.server``/``self.coord`` to the new leader transparently.
    """

    def __init__(self, *, hosts=3, n=480, gb=12, faults=None, ttl=4.0,
                 heartbeat_timeout=6.0, link_config=None, **cfg_kw):
        from repro.data import DataLoader, LoaderParams
        from repro.tuning import (FaultSpec, FaultyTransport, FleetConfig,
                                  FleetCoordinator, LeaderLease, LinkConfig,
                                  SnapshotStore, connect_host)
        from repro.tuning.fleet import CoordinatorReplica, CoordinatorServer

        self.n, self.gb = n, gb
        self.bpe = n // gb
        self.clock = [0.0]
        ck = lambda: self.clock[0]  # noqa: E731
        self.transport = FaultyTransport(faults or FaultSpec())
        self.lease = LeaderLease(ttl_s=ttl, clock=ck)
        self.store = SnapshotStore()
        defaults = dict(heartbeat_timeout_s=heartbeat_timeout,
                        warmup_steps=2, cooldown_steps=4, num_cpu_cores=4,
                        num_devices=1, max_prefetch=2,
                        retune_budget_batches=2)
        defaults.update(cfg_kw)
        self.coord = FleetCoordinator(config=FleetConfig(**defaults),
                                      clock=ck)
        self.server = CoordinatorServer(self.coord, self.transport,
                                        owner="coord-0", lease=self.lease,
                                        store=self.store)
        self.replica = CoordinatorReplica(self.transport, self.lease,
                                          self.store, owner="coord-standby",
                                          clock=ck)
        self.agents, self.streams = [], []
        for h in range(hosts):
            dl = DataLoader(make_index_dataset(n), gb, shuffle=True, seed=5,
                            params=LoaderParams(num_workers=2,
                                                prefetch_factor=2),
                            host_index=h, host_count=hosts)
            self.agents.append(connect_host(
                self.transport, f"host{h}", dl,
                evaluator=make_table_evaluator(
                    lambda i, j: 4.0 / i + 0.1 * j),
                clock=ck,
                link_config=link_config or LinkConfig(seed=h, jitter=0.0)))
            self.streams.append(dl.stream(to_device=False))
        # deliver any setup message a delay fault parked (a stale register
        # replayed mid-run would be a different, rarer anomaly)
        self.transport.pump()
        self.delivered = []

    def rounds(self, k, alive=None, *, poll=True):
        alive = list(alive if alive is not None else range(len(self.agents)))
        for _ in range(k):
            self.clock[0] += 1.0
            for h in alive:
                self.delivered.append(next(self.streams[h]))
                self.agents[h].observe(data_s=0.001, step_s=0.05)
            self.transport.pump()
            self.server.tick()
            if poll:
                self.server.poll()
            promoted = self.replica.tick()
            if promoted is not None:
                self.server = promoted
                self.coord = promoted.coord

    def drain(self, alive):
        for h in alive:
            s = self.streams[h]
            while s.position < self.bpe:
                self.delivered.append(next(s))

    def close(self):
        for s in self.streams:
            try:
                s.close()
            except Exception:
                pass


@pytest.fixture
def wire_fleet():
    """Factory fixture for :class:`WireFleet`; streams close at teardown."""
    fleets = []

    def build(**kw):
        f = WireFleet(**kw)
        fleets.append(f)
        return f

    yield build
    for f in fleets:
        f.close()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-device subprocess tests (minutes, not seconds)")


# --------------------------------------------------------------------------
# per-test duration accounting (CI budget gate, see check_durations.py)
# --------------------------------------------------------------------------
_durations = {}


def pytest_runtest_logreport(report):
    if report.when == "call":
        _durations[report.nodeid] = report.duration


def pytest_sessionfinish(session, exitstatus):
    path = os.environ.get("REPRO_DURATIONS_JSON")
    if path and _durations:
        with open(path, "w") as f:
            json.dump({k: round(v, 3) for k, v in _durations.items()},
                      f, indent=1, sort_keys=True)
