"""JAX delta-staleness engine: Sec-7 semantics on SPMD-style training."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.staleness import init_delayed_state, make_delayed_step
from repro.optim import OptConfig, make_optimizer


def _toy_problem(seed=0, dim=8):
    key = jax.random.PRNGKey(seed)
    A = jax.random.normal(key, (32, dim)) / np.sqrt(dim)
    w_true = jax.random.normal(jax.random.PRNGKey(seed + 1), (dim,))
    y = A @ w_true

    def grad_fn(params, batch):
        def loss(p):
            r = batch["A"] @ p["w"] - batch["y"]
            return 0.5 * jnp.mean(r * r)
        l, g = jax.value_and_grad(loss)(params)
        return l, g

    return {"w": jnp.zeros((dim,))}, {"A": A, "y": y}, grad_fn


@pytest.mark.parametrize("opt_name", ["sgd", "adamw"])
def test_delta0_bit_identical_to_sync(opt_name):
    """The paper's central guarantee mapped to steps: delta=0 == synchronous
    training exactly (both sides jitted — comparing jit to eager would only
    measure XLA fusion noise, not the engine)."""
    params, batch, grad_fn = _toy_problem()
    opt = make_optimizer(OptConfig(name=opt_name, lr=0.1, grad_clip=0,
                                   weight_decay=0.0))

    @jax.jit
    def sync_step(p, s, b):
        loss, g = grad_fn(p, b)
        new = opt.update(g, s, p)
        # the engine's guard (a non-finite step keeps the prior values);
        # without it XLA fuses the update differently and rounds apart
        ok = jnp.isfinite(loss)
        return jax.tree.map(lambda n, o: jnp.where(ok, n, o), new, (p, s))

    p_sync, s_sync = params, opt.init(params)
    for _ in range(10):
        p_sync, s_sync = sync_step(p_sync, s_sync, batch)

    # delayed engine with delta=0
    step = jax.jit(make_delayed_step(grad_fn, opt.update, delta=0))
    state = init_delayed_state(params, opt.init, delta=0)
    for _ in range(10):
        state, m = step(state, batch)

    np.testing.assert_array_equal(np.asarray(p_sync["w"]),
                                  np.asarray(state.params["w"]))


def test_delta_matches_manual_delayed_gd():
    """delta=2 must equal hand-rolled delayed gradient descent:
    w[t+1] = w[t] - lr * grad(w[t-2])."""
    params, batch, grad_fn = _toy_problem(seed=3)
    lr, delta, steps = 0.05, 2, 12
    opt = make_optimizer(OptConfig(name="sgd", lr=lr, grad_clip=0))

    hist = [np.asarray(params["w"])] * (delta + 1)
    w = np.asarray(params["w"])
    for t in range(steps):
        stale = hist[0]
        _, g = grad_fn({"w": jnp.asarray(stale)}, batch)
        w = w - lr * np.asarray(g["w"])
        hist = hist[1:] + [w]

    step = jax.jit(make_delayed_step(grad_fn, opt.update, delta=delta))
    state = init_delayed_state(params, opt.init, delta=delta)
    for _ in range(steps):
        state, _ = step(state, batch)

    np.testing.assert_allclose(np.asarray(state.params["w"]), w,
                               rtol=1e-6, atol=1e-7)


def test_delta_converges_on_convex():
    params, batch, grad_fn = _toy_problem(seed=5)
    opt = make_optimizer(OptConfig(name="sgd", lr=0.2, grad_clip=0))
    step = jax.jit(make_delayed_step(grad_fn, opt.update, delta=3))
    state = init_delayed_state(params, opt.init, delta=3)
    first = None
    for _ in range(60):
        state, m = step(state, batch)
        first = float(m["loss"]) if first is None else first
    assert float(m["loss"]) < 0.2 * first


def _mixed_tree(seed=11):
    """A pytree with several leaves of different shapes/dtypes and a grad_fn
    over all of them — exercises the packed ring's (delay, dtype) grouping."""
    key = jax.random.PRNGKey(seed)
    A = jax.random.normal(key, (32, 8)) / np.sqrt(8)
    y = A @ jax.random.normal(jax.random.PRNGKey(seed + 1), (8,))
    params = {
        "w": jnp.zeros((8,)),
        "m": jnp.zeros((4, 8)),
        "b": jnp.zeros((1,)),
        "h": jnp.zeros((8,), jnp.bfloat16),
    }

    def grad_fn(p, batch_):
        def loss(pp):
            w = pp["w"] + pp["m"].mean(0) + pp["h"].astype(jnp.float32)
            r = batch_["A"] @ w + pp["b"] - batch_["y"]
            return 0.5 * jnp.mean(r * r)
        l, g = jax.value_and_grad(loss)(p)
        return l, g

    return params, {"A": A, "y": y}, grad_fn


def _run_trajectory(packed, delta, delay_for=None, steps=8, seed=11,
                    opt_name="adamw"):
    params, batch, grad_fn = _mixed_tree(seed)
    opt = make_optimizer(OptConfig(name=opt_name, lr=0.1, grad_clip=0,
                                   weight_decay=0.0))
    step = jax.jit(make_delayed_step(grad_fn, opt.update, delta=delta,
                                     delay_for=delay_for, packed=packed))
    state = init_delayed_state(params, opt.init, delta=delta, packed=packed,
                               delay_for=delay_for)
    stale0 = step.read_stale(state)
    for _ in range(steps):
        state, m = step(state, batch)
    return stale0, state.params, m


@pytest.mark.parametrize("delta", [0, 1, 2, 3])
def test_packed_ring_bit_identical_to_tree(delta):
    """The packed (delay, dtype)-grouped ring buffer must reproduce the
    per-leaf tree ring exactly — reads and full trajectories, every delta."""
    s_tree, p_tree, _ = _run_trajectory(packed=False, delta=delta)
    s_pack, p_pack, _ = _run_trajectory(packed=True, delta=delta)
    for k in p_tree:
        np.testing.assert_array_equal(np.asarray(s_tree[k]),
                                      np.asarray(s_pack[k]), err_msg=k)
        np.testing.assert_array_equal(np.asarray(p_tree[k]),
                                      np.asarray(p_pack[k]), err_msg=k)


def test_packed_ring_mixed_delays_bit_identical():
    """Per-group delays (Sec 7.1) land leaves in different packed groups;
    the layouts must still agree bit-for-bit."""
    def delay_for(path):
        name = path[0].key if hasattr(path[0], "key") else str(path[0])
        return {"w": 0, "m": 2, "b": 1, "h": 3}[name]

    s_tree, p_tree, _ = _run_trajectory(packed=False, delta=3,
                                        delay_for=delay_for)
    s_pack, p_pack, _ = _run_trajectory(packed=True, delta=3,
                                        delay_for=delay_for)
    for k in p_tree:
        np.testing.assert_array_equal(np.asarray(s_tree[k]),
                                      np.asarray(s_pack[k]), err_msg=k)
        np.testing.assert_array_equal(np.asarray(p_tree[k]),
                                      np.asarray(p_pack[k]), err_msg=k)


def test_packed_ring_pallas_gather_matches_ref(monkeypatch):
    """With REPRO_KERNEL_IMPL=interpret the packed read path runs the Pallas
    ring-gather kernel (emulated) — must stay bit-identical to the XLA ref."""
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "ref")
    _, p_ref, _ = _run_trajectory(packed=True, delta=2, steps=5)
    monkeypatch.setenv("REPRO_KERNEL_IMPL", "interpret")
    _, p_int, _ = _run_trajectory(packed=True, delta=2, steps=5)
    for k in p_ref:
        np.testing.assert_array_equal(np.asarray(p_ref[k]),
                                      np.asarray(p_int[k]), err_msg=k)


def test_per_group_delays():
    """Sec-7.1 per-chunk version arrays: different param groups can read
    different staleness levels."""
    params, batch, grad_fn0 = _toy_problem(seed=7)
    params = {"a": params["w"], "b": params["w"] + 1.0}

    def grad_fn(p, batch_):
        def loss(pp):
            r = batch_["A"] @ (pp["a"] + pp["b"]) - batch_["y"]
            return 0.5 * jnp.mean(r * r)
        l, g = jax.value_and_grad(loss)(p)
        return l, g

    opt = make_optimizer(OptConfig(name="sgd", lr=0.1, grad_clip=0))

    def delay_for(path):
        name = path[0].key if hasattr(path[0], "key") else str(path[0])
        return 0 if name == "a" else 2

    step = jax.jit(make_delayed_step(grad_fn, opt.update, delta=2,
                                     delay_for=delay_for))
    state = init_delayed_state(params, opt.init, delta=2)
    for _ in range(5):
        state, m = step(state, batch)
    assert jnp.isfinite(m["loss"])
    # group 'a' read fresh params; 'b' read 2-step-stale ones — verify the
    # trajectories differ from uniform delta in a controlled way
    step_u = jax.jit(make_delayed_step(grad_fn, opt.update, delta=2))
    state_u = init_delayed_state(params, opt.init, delta=2)
    for _ in range(5):
        state_u, _ = step_u(state_u, batch)
    assert not np.allclose(np.asarray(state.params["a"]),
                           np.asarray(state_u.params["a"]))
