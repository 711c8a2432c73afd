"""Straggler monitor + sharded loader (elastic-scale substrate units)."""
import numpy as np
import pytest

from repro.train.straggler import StragglerMonitor


def _feed(mon, times_by_worker, steps=10):
    for s in range(steps):
        for w, t in enumerate(times_by_worker):
            mon.record(w, t * (1.0 + 0.01 * (s % 3)))


def test_no_stragglers_on_uniform_fleet():
    mon = StragglerMonitor(n_workers=8)
    _feed(mon, [1.0] * 8)
    assert mon.stragglers() == []
    assert mon.shard_assignment() == list(range(8))


def test_straggler_detected_and_shard_swapped():
    mon = StragglerMonitor(n_workers=8, threshold=1.5)
    times = [1.0] * 8
    times[3] = 2.5                      # worker 3 runs 2.5x slower
    _feed(mon, times)
    assert mon.stragglers() == [3]
    assignment = mon.shard_assignment()
    # worker 3 no longer owns shard 3; a healthy fast worker does
    assert assignment[3] != 3
    assert sorted(assignment) == list(range(8))   # permutation (no data loss)


def test_assignment_deterministic():
    """Every host must compute the SAME assignment (no coordinator)."""
    def build():
        m = StragglerMonitor(n_workers=6, threshold=1.4)
        times = [1.0, 1.0, 3.0, 1.0, 1.1, 0.9]
        _feed(m, times)
        return m.shard_assignment()
    assert build() == build()


def test_warmup_suppresses_flags():
    mon = StragglerMonitor(n_workers=4, warmup_steps=5)
    for w in range(4):
        mon.record(w, 10.0 if w == 0 else 1.0)
    assert mon.stragglers() == []       # only 1 sample each


def test_summary_shape():
    mon = StragglerMonitor(n_workers=3)
    _feed(mon, [1.0, 1.0, 5.0])
    s = mon.summary()
    assert len(s["ewma"]) == 3 and s["stragglers"] == [2]


def test_loader_reassign():
    from repro.data import ShardedLoader, SyntheticTokenDataset
    from repro.launch.mesh import make_mesh
    ds = SyntheticTokenDataset(64, 8, seed=1)
    mesh = make_mesh((1,), ("data",))
    loader = ShardedLoader(
        lambda step, bs, shard, n: {"tokens": ds.batch(step, bs, shard, n)},
        global_batch=4, mesh=mesh, n_shards=4, shard=0)
    a = np.asarray(loader(3)["tokens"])
    loader.reassign(shard=2, n_shards=4)
    b = np.asarray(loader(3)["tokens"])
    assert not np.array_equal(a, b)     # different shard, same step
    loader.reassign(shard=0, n_shards=4)
    c = np.asarray(loader(3)["tokens"])
    np.testing.assert_array_equal(a, c)  # replay-safe
