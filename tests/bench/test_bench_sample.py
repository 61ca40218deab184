"""The sample of the window's answers, the check of every query
trajectory of a kept answer, and the compile monitor."""
import types

import numpy as np
import pytest

import _bench_tiny  # noqa: F401 (puts the checkout root on sys.path)
from bench import cells, datagen, harness, reference
from bench.traffic import common


def test_reservoir_is_the_seeds_and_keeps_its_size():
    def draw(seed, n):
        r = common.Reservoir(2, np.random.default_rng(seed))
        for i in range(n):
            r.offer(i)
        return sorted(r.items)

    assert draw(5, 1) == [0]
    assert draw(5, 2) == [0, 1]
    assert draw(5, 30) == draw(5, 30) and len(draw(5, 30)) == 2


def test_reservoir_covers_the_whole_window():
    n, trials = 10, 4000
    counts = np.zeros(n)
    rng = np.random.default_rng(11)
    for _ in range(trials):
        r = common.Reservoir(2, rng)
        for i in range(n):
            r.offer(i)
        counts[r.items] += 1
    # Every execution is kept with probability 2 / n, the last as often
    # as the first.
    assert np.all(np.abs(counts / trials - 2 / n) < 0.03)


def _ctx(seed=4):
    data = cells.dataset("galaxy").generate(seed, num_traj=12,
                                            num_segments=30)
    ctx = types.SimpleNamespace(data=data, d=0.8, control=None,
                                index=reference.EntryIndex(data))
    return ctx


def _answer(ctx, trajs):
    """The exact rows of query trajectories ``trajs``, as the program
    reports them."""
    rows = ctx.data.rows_of(trajs)
    cols = {c: ctx.data.cols[c][rows] for c in datagen.COLUMNS}
    truth = reference.reference(ctx.index, cols, np.arange(len(rows)),
                                ctx.d)
    key = truth.key[truth.hit]
    q, e = np.divmod(key, len(ctx.data))
    ans = {"query_idx": q, "entry_traj": ctx.data.traj_id[e].astype(np.int64),
           "entry_seg": ctx.data.seg_id[e].astype(np.int64),
           "t_enter": truth.t_enter[truth.hit].astype(np.float32),
           "t_exit": truth.t_exit[truth.hit].astype(np.float32)}
    lengths = [len(ctx.data.rows_of([t])) for t in trajs]
    return cols, lengths, ans


def _check(ctx, cols, lengths, ans):
    return reference.worst(common.check_result(
        ctx, cols, lengths, ans["query_idx"], ans["entry_traj"],
        ans["entry_seg"], ans["t_enter"], ans["t_exit"]))


def test_every_trajectory_of_an_answer_is_checked():
    ctx = _ctx()
    cols, lengths, ans = _answer(ctx, [3, 7, 1])
    assert len(ans["query_idx"]) > 0
    got = _check(ctx, cols, lengths, ans)
    assert got["miss_depth"] == got["extra_depth"] == 0.0
    assert got["t_gap"] < 1e-4              # float32 intervals
    # A row dropped from the last trajectory is missed.
    last = np.flatnonzero(ans["query_idx"] >= sum(lengths[:2]))
    cut = {k: np.delete(v, last[-1]) for k, v in ans.items()}
    assert _check(ctx, cols, lengths, cut)["miss_depth"] > 0


@pytest.mark.parametrize("q_pos", [-1, 90])
def test_a_row_outside_every_trajectory_is_extra(q_pos):
    ctx = _ctx()
    cols, lengths, ans = _answer(ctx, [3, 7, 1])
    assert sum(lengths) == 90
    bad = {k: np.concatenate([v, v[:1]]) for k, v in ans.items()}
    bad["query_idx"][-1] = q_pos
    assert _check(ctx, cols, lengths, bad)["extra_depth"] >= 1e9


def test_compile_seconds_count_tracing_and_lowering():
    import jax
    import jax.numpy as jnp
    mon = harness.CompileMonitor.get()
    snap = mon.snapshot()
    jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    got = mon.since(snap)
    assert got["lowerings"] >= 1 and got["compile_s"] > 0
