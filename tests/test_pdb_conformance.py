"""Policy x backend conformance matrix for the unified ParameterDB.

Every consistency policy must behave identically through every execution
backend:

  * at delta=0, the sequentially-correct policies (bsp, dc, dc-array) must
    produce final parameters **bit-identical** to single-threaded sequential
    execution, through both the in-process replay backend and the real
    threaded backend;
  * every recorded history (any backend) must be complete and satisfy
    ``history.is_sequentially_correct`` — the single semantic oracle;
  * the SSP policy must respect its clock bound (slack) under the
    ``random_schedule`` property fuzzer and on real threads, while *not*
    being required to be sequentially correct;
  * the JAX ring-buffer backend must agree with ``sequential_result``-style
    ground truth at delta=0 and emit the same kind of Op history;
  * the multi-process sharded backend (``repro.pdb.server``: real shard
    processes + socket RPC + client caches + clock gossip) must meet the
    same bar — delta=0 bit-identity, merged-global-history oracle, SSP
    clock bound — plus distributed-only invariants: the merged history is
    an order-preserving merge of the per-shard histories, and cache hits
    never change results.
"""
import numpy as np
import pytest

from repro.core import history as H
from repro.core import threaded as T
from repro.pdb import (InProcessParameterDB, InadmissibleOp, SSPPolicy,
                       ThreadedParameterDB, make_policy, random_schedule,
                       run_interleaved, ssp_clock_bound_violations)
from repro.pdb.server import ShardCluster, run_distributed_lr

SEQ_POLICIES = ["bsp", "dc", "dc-array"]   # sequentially correct at delta=0
ALL_POLICIES = SEQ_POLICIES + ["ssp", "hogwild"]


@pytest.fixture(scope="module")
def data():
    return T.make_synthetic_lr(120, 24, seed=0)


def _task(data, **kw):
    X, y = data
    kw.setdefault("n_iters", 6)
    return T.LRTask(X, y, mode="gd", **kw)


def _inprocess_theta(task, n_workers, policy, delta=0, seed=0):
    slices = T.chunk_slices(task.X.shape[1], n_workers)
    schedule = task.sample_schedule()
    init = [np.zeros(sl.stop - sl.start) for sl in slices]
    db = InProcessParameterDB(
        init, n_workers,
        policy=make_policy(policy, n_workers, delta, n_chunks=n_workers),
        record=True)

    def update(worker, snap, itr):
        return T.chunk_update(task, snap, slices[worker], itr, schedule)

    theta = run_interleaved(db, task.n_iters, update, seed=seed)
    return theta, db


# ---------------------------------------------------------------------------
# delta=0 bit-identity + history oracle, for every (policy, backend) pair
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", SEQ_POLICIES)
@pytest.mark.parametrize("workers", [2, 4])
def test_inprocess_delta0_bit_identical(data, policy, workers):
    task = _task(data)
    seq = T.run_sequential(task, workers)
    for seed in range(3):           # three different interleavings
        theta, db = _inprocess_theta(task, workers, policy, seed=seed)
        assert np.array_equal(theta, seq)
        assert H.is_complete(db.history, workers, task.n_iters)
        assert H.is_sequentially_correct(db.history, workers)


@pytest.mark.parametrize("policy", SEQ_POLICIES)
@pytest.mark.parametrize("workers", [2, 4])
def test_threaded_delta0_bit_identical(data, policy, workers):
    task = _task(data)
    seq = T.run_sequential(task, workers)
    stats = T.run_parallel(task, workers, policy=policy, record_history=True)
    assert np.array_equal(stats.theta, seq)
    assert H.is_complete(stats.history, workers, task.n_iters)
    assert H.is_sequentially_correct(stats.history, workers)
    # exact policies never serve a stale or read-ahead value
    assert stats.staleness["max_staleness"] == 0
    assert stats.staleness["stale_reads"] == 0
    assert stats.staleness["ahead_reads"] == 0


@pytest.mark.parametrize("backend", ["inproc", "threaded"])
def test_delta_relaxed_still_converges(data, backend):
    task = _task(data, n_iters=25, lr=0.3)
    if backend == "threaded":
        theta = T.run_parallel(task, 4, policy="dc", delta=2).theta
    else:
        theta, _ = _inprocess_theta(task, 4, "dc", delta=2, seed=1)
    init_loss = T.loss(task, np.zeros(task.X.shape[1]))
    assert T.loss(task, theta) < 0.9 * init_loss


# ---------------------------------------------------------------------------
# The same telemetry flows through every backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_op_counts_uniform_across_backends(data, policy):
    task = _task(data, n_iters=4)
    p = 3
    delta = 1 if policy in ("dc", "dc-array", "ssp") else 0
    _, db = _inprocess_theta(task, p, policy, delta=delta, seed=0)
    stats = T.run_parallel(task, p, policy=policy, delta=delta,
                           record_history=True)
    want_reads, want_writes = p * p * task.n_iters, p * task.n_iters
    for s in (db.telemetry.summary(), stats.staleness):
        assert s["reads"] == want_reads
        assert s["writes"] == want_writes


# ---------------------------------------------------------------------------
# SSP: clock bound respected, under the fuzzer and on real threads
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("slack", [0, 1, 3])
@pytest.mark.parametrize("p,n", [(2, 4), (4, 3)])
def test_ssp_clock_bound_random_schedule(slack, p, n):
    for seed in range(8):
        h = random_schedule("ssp", p, n, seed=seed, delta=slack)
        assert len(h) == p * n * (p + 1)       # total progress
        assert ssp_clock_bound_violations(h, p, slack) == []


def test_ssp_random_schedule_can_exceed_smaller_bound():
    """The fuzzer actually exercises the slack: with slack=3 some schedule
    violates the slack=1 bound (otherwise the bound test is vacuous)."""
    found = False
    for seed in range(20):
        h = random_schedule("ssp", 3, 4, seed=seed, delta=3)
        if ssp_clock_bound_violations(h, 3, 1):
            found = True
            break
    assert found


def test_ssp_threaded_respects_bound(data):
    task = _task(data, n_iters=8)
    stats = T.run_parallel(task, 4, policy="ssp", delta=2,
                           record_history=True)
    assert H.is_complete(stats.history, 4, 8)
    assert ssp_clock_bound_violations(stats.history, 4, 2) == []


def test_ssp_policy_admission_unit():
    s = SSPPolicy(2, slack=1)
    assert s.can_read(0, 0, 1) and s.can_read(0, 0, 2)   # within slack
    assert not s.can_read(0, 0, 3)                       # min clock 0 < 3-1-1
    assert s.can_write(0, 0, 99)                         # writes never gated
    s.did_write(1, 1, 1)
    assert not s.can_read(0, 0, 3)                       # worker 0 still at 0
    s.did_write(0, 0, 1)
    assert s.can_read(0, 0, 3)
    with pytest.raises(ValueError):
        SSPPolicy(2, slack=-1)


# ---------------------------------------------------------------------------
# In-process backend: inadmissible ops raise instead of blocking
# ---------------------------------------------------------------------------

def test_inprocess_raises_on_inadmissible():
    db = InProcessParameterDB([np.zeros(2), np.zeros(2)], 2, policy="dc")
    with pytest.raises(InadmissibleOp):
        db.read(0, 0, 2)            # nothing written yet: version 0 != 1
    db.read(0, 0, 1)
    with pytest.raises(InadmissibleOp):
        db.write(0, 0, 1, np.ones(2))   # worker 1 hasn't read chunk 0


def test_threaded_db_timeout_surfaces_deadlock():
    db = ThreadedParameterDB([np.zeros(1)], 1, policy="dc", timeout=0.05)
    with pytest.raises(RuntimeError, match="timed out"):
        db.read(0, 0, 5)            # never admissible: nobody writes


# ---------------------------------------------------------------------------
# JAX ring-buffer backend through the unified engine
# ---------------------------------------------------------------------------

def _toy_engine(delta, group_delays=(), record=True):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.core.sync_jax import SyncConfig
    from repro.optim import OptConfig, make_optimizer
    from repro.pdb import make_engine

    dim = 6
    A = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (24, 2 * dim)))
    ytrue = A @ np.ones(2 * dim)
    batch = {"A": jnp.asarray(A), "y": jnp.asarray(ytrue)}
    params = {"a": jnp.zeros((dim,)), "b": jnp.zeros((dim,))}

    def grad_fn(p, b):
        def loss_fn(pp):
            r = b["A"] @ jnp.concatenate([pp["a"], pp["b"]]) - b["y"]
            return 0.5 * jnp.mean(r * r)
        return jax.value_and_grad(loss_fn)(p)

    opt = make_optimizer(OptConfig(name="sgd", lr=0.05, grad_clip=0))
    sync = SyncConfig(delta=delta, group_delays=group_delays)
    eng = make_engine(params, grad_fn, opt, sync, record_history=record)
    return eng, batch


def test_jax_engine_delta0_matches_sequential_and_history():
    eng, batch = _toy_engine(delta=0)
    state = eng.init_state()
    n = 8
    for _ in range(n):
        state, m = eng.step(state, batch)
    # ground truth: plain full-batch GD on the same problem
    w = np.zeros(12)
    A = np.asarray(batch["A"]); y = np.asarray(batch["y"])
    for _ in range(n):
        w = w - 0.05 * (A.T @ (A @ w - y)) / A.shape[0]
    got = np.concatenate([np.asarray(state["params"]["a"]),
                          np.asarray(state["params"]["b"])])
    np.testing.assert_allclose(got, w, rtol=1e-6, atol=1e-7)
    # same Op-history oracle as the host backends (2 groups = 2 chunks)
    assert H.is_sequentially_correct(eng.history, 2)
    assert len(eng.history) == n * (2 + 2)
    assert eng.telemetry.summary()["max_staleness"] == 0


def test_jax_engine_group_delays_telemetry():
    eng, batch = _toy_engine(delta=2, group_delays=(("a", 0),))
    state = eng.init_state()
    for _ in range(6):
        state, m = eng.step(state, batch)
    s = eng.telemetry.summary()
    assert eng.group_delays == (0, 2)       # leaf 'a' fresh, 'b' stale
    assert s["max_staleness"] == 2
    assert s["stale_reads"] > 0
    assert np.isfinite(float(m["loss"]))


@pytest.mark.parametrize("delta", [0, 2])
def test_jax_engine_nonfinite_step_keeps_state(delta):
    """The engine's step donates its state, so a poisoned step must hand
    the prior values back itself: parameters, ring pointer and step count
    come out exactly as they went in."""
    jax = pytest.importorskip("jax")
    eng, batch = _toy_engine(delta=delta, record=False)
    state = eng.init_state()
    state, _ = eng.step(state, batch)
    before = jax.tree.map(np.asarray, state)
    poisoned = dict(batch, y=batch["y"].at[0].set(float("nan")))
    state, m = eng.step_fn(state, poisoned)
    assert not np.isfinite(float(m["loss"]))
    jax.tree.map(np.testing.assert_array_equal, before,
                 jax.tree.map(np.asarray, state))
    state, m = eng.step_fn(state, batch)            # training resumes
    assert np.isfinite(float(m["loss"]))


# ---------------------------------------------------------------------------
# The multi-process sharded backend (repro.pdb.server)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("batched", [False, True], ids=["per-op", "batched"])
@pytest.mark.parametrize("policy", SEQ_POLICIES)
def test_server_delta0_bit_identical(data, policy, batched):
    """Real shard processes, socket RPC, client caches — and still
    bit-identical to single-threaded sequential execution at delta=0,
    on both the per-chunk v1 path and the batched/pipelined v2 path."""
    task = _task(data)
    workers = 4
    seq = T.run_sequential(task, workers)
    res = run_distributed_lr(task, workers, n_shards=2, policy=policy,
                             delta=0, batched=batched)
    assert np.array_equal(res.theta, seq)
    assert H.is_complete(res.history, workers, task.n_iters)
    assert H.is_sequentially_correct(res.history, workers)
    assert res.staleness["max_staleness"] == 0
    assert res.staleness["stale_reads"] == 0


@pytest.mark.parametrize("batched", [False, True], ids=["per-op", "batched"])
def test_server_delta_relaxed_cache_hits(data, batched):
    """delta>0 must respect the staleness bound, and the client cache must
    actually serve reads (admissible cached versions skip the payload) —
    as piggybacked ``notify`` batch entries on the v2 path."""
    task = _task(data, n_iters=8)
    res = run_distributed_lr(task, 4, n_shards=2, policy="dc-array", delta=1,
                             batched=batched)
    assert res.staleness["max_staleness"] <= 1
    assert res.cache["cache_hits"] > 0
    assert res.cache["bytes_saved"] > 0
    init_loss = T.loss(task, np.zeros(task.X.shape[1]))
    assert T.loss(task, res.theta) < init_loss


@pytest.mark.parametrize("batched", [False, True], ids=["per-op", "batched"])
def test_server_ssp_clock_bound(data, batched):
    """SSP on first-class per-worker clocks: the slack bound must hold on
    the merged global history exactly as it does in-process."""
    task = _task(data, n_iters=8)
    res = run_distributed_lr(task, 4, n_shards=2, policy="ssp", delta=2,
                             batched=batched)
    assert H.is_complete(res.history, 4, 8)
    assert ssp_clock_bound_violations(res.history, 4, 2) == []
    assert res.staleness["max_staleness"] <= 2


@pytest.mark.parametrize("batched", [False, True], ids=["per-op", "batched"])
def test_server_op_counts_match_other_backends(data, batched):
    task = _task(data, n_iters=4)
    p = 3
    res = run_distributed_lr(task, p, n_shards=2, policy="hogwild",
                             batched=batched)
    assert res.staleness["reads"] == p * p * task.n_iters
    assert res.staleness["writes"] == p * task.n_iters
    assert H.is_complete(res.history, p, task.n_iters)


def test_server_merged_history_is_order_preserving(data):
    """The global history must be an order-preserving merge of the
    per-shard histories (each shard's local order is authoritative for
    the chunks it owns) — the invariant that makes
    ``is_sequentially_correct`` sound on the merged history."""
    task = _task(data, n_iters=4)
    workers = 4
    slices = T.chunk_slices(task.X.shape[1], workers)
    schedule = task.sample_schedule()
    init = [np.zeros(sl.stop - sl.start) for sl in slices]
    with ShardCluster(init, workers, n_shards=3, policy="dc",
                      delta=0) as cluster:
        import threading

        def worker(i, db):
            for itr in range(1, task.n_iters + 1):
                theta = np.concatenate(db.read_all(i, itr))
                db.write(i, i, itr,
                         T.chunk_update(task, theta, slices[i], itr,
                                        schedule))
            db.close()

        threads = [threading.Thread(
            target=worker, args=(i, cluster.make_client(i)), daemon=True)
            for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        pulled = cluster.pull()
    parts = [[op for _, op in part] for part in pulled.per_shard]
    assert H.is_order_preserving_merge(pulled.history, parts)
    assert H.is_sequentially_correct(pulled.history, workers)
    # chunk ownership is a partition: each op recorded on exactly one shard
    for shard_idx, part in enumerate(parts):
        from repro.pdb.server import shard_of
        assert all(shard_of(op.chunk, 3) == shard_idx for op in part)
