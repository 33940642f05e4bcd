"""Server optimizers vs hand-computed references (SURVEY.md §5: "each
optimizer vs a NumPy/optax reference")."""

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import ps_tpu as ps
from ps_tpu.optim import make_optimizer


def run_steps(opt_name, steps=5, **kw):
    """Run the same gradient sequence through the local PS and through a
    plain optax loop; return both parameter trajectories."""
    ps.init(backend="local")
    store = ps.KVStore(optimizer=opt_name, **kw)
    w0 = jnp.array([1.0, -2.0, 3.0])
    store.init({"w": w0})

    opt = make_optimizer(opt_name, **kw)
    ref_w = w0
    ref_state = opt.init(ref_w)

    ps_traj, ref_traj = [], []
    for i in range(steps):
        g = jnp.array([0.1 * (i + 1), -0.2, 0.3 * (i % 2)])
        store.push("w", g)
        ps_traj.append(np.asarray(store.pull("w")))
        updates, ref_state = opt.update(g, ref_state, ref_w)
        ref_w = optax.apply_updates(ref_w, updates)
        ref_traj.append(np.asarray(ref_w))
    return ps_traj, ref_traj


@pytest.mark.parametrize("opt_name,kw", [
    ("sgd", {"learning_rate": 0.1}),
    ("momentum", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.01}),
    ("lamb", {"learning_rate": 0.01, "weight_decay": 0.01}),
    ("adamw", {"learning_rate": 0.01, "b2": 0.95, "weight_decay": 0.1}),
    ("adamw", {"learning_rate": 0.01, "weight_decay": 0.1,
               "clip_by_global_norm": 0.25}),
])
def test_server_apply_matches_optax(opt_name, kw):
    ps_traj, ref_traj = run_steps(opt_name, **kw)
    for a, b in zip(ps_traj, ref_traj):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("clip", [None, 0.25, 100.0])
def test_adamw_exact_math(clip):
    """Decoupled decay on every tensor behind a clip of the WHOLE tree's
    gradient norm: one step by hand. Adam's first step is lr * sign(g)
    whatever the clip's scale, so the clip shows on the second."""
    lr, b1, b2, eps, wd = 0.1, 0.9, 0.95, 1e-8, 0.1
    opt = make_optimizer("adamw", learning_rate=lr, b1=b1, b2=b2, eps=eps,
                         weight_decay=wd, clip_by_global_norm=clip)
    params = {"a": np.array([1.0, -2.0]), "b": np.array([[3.0]])}
    grads = [{"a": np.array([0.3, -0.4]), "b": np.array([[1.2]])},   # norm 1.3
             {"a": np.array([0.03, 0.04]), "b": np.array([[0.0]])}]  # 0.05
    tree = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    state = opt.init(tree)
    m = {k: np.zeros_like(v) for k, v in params.items()}
    v = {k: np.zeros_like(x) for k, x in params.items()}
    for t, g in enumerate(grads, start=1):
        updates, state = opt.update(
            {k: jnp.asarray(x, jnp.float32) for k, x in g.items()}, state,
            tree)
        tree = optax.apply_updates(tree, updates)
        norm = np.sqrt(sum(np.sum(x * x) for x in g.values()))
        scale = 1.0 if clip is None else min(1.0, clip / norm)
        for k in params:
            m[k] = b1 * m[k] + (1 - b1) * g[k] * scale
            v[k] = b2 * v[k] + (1 - b2) * (g[k] * scale) ** 2
            step = (m[k] / (1 - b1 ** t)) / (
                np.sqrt(v[k] / (1 - b2 ** t)) + eps)
            params[k] = params[k] - lr * (step + wd * params[k])
            np.testing.assert_allclose(np.asarray(tree[k]), params[k],
                                       rtol=1e-5)
    if clip == 0.25:   # only the first gradient (norm 1.3) was scaled
        assert m["a"][0] == pytest.approx(
            b1 * (1 - b1) * 0.3 * 0.25 / 1.3 + (1 - b1) * 0.03)


def test_sgd_exact_math():
    ps.init(backend="local")
    store = ps.KVStore(optimizer="sgd", learning_rate=0.5)
    store.init({"w": jnp.array([10.0])})
    store.push("w", jnp.array([4.0]))
    np.testing.assert_allclose(np.asarray(store.pull("w")), [8.0])


def test_custom_optax_transformation():
    ps.init(backend="local")
    store = ps.KVStore(optimizer=optax.adamw(1e-2, weight_decay=0.1))
    store.init({"w": jnp.ones(2)})
    store.push("w", jnp.ones(2))
    out = np.asarray(store.pull("w"))
    assert np.all(out < 1.0)


def test_unknown_name_raises():
    ps.init(backend="local")
    with pytest.raises(ValueError, match="unknown optimizer"):
        ps.KVStore(optimizer="adagrad9000")


def test_per_key_state_is_independent():
    """Adam state (incl. step count) is tracked per key, like the reference
    server's per-key state tables."""
    ps.init(backend="local")
    store = ps.KVStore(optimizer="adam", learning_rate=0.1)
    store.init({"a": jnp.zeros(2), "b": jnp.zeros(2)})
    for _ in range(3):
        store.push("a", jnp.ones(2))
        store.pull("a")
    store.push("b", jnp.ones(2))
    # 'b' has seen one update; its Adam moments differ from 'a's
    state_a = store.optimizer_state("a")
    state_b = store.optimizer_state("b")
    count_a = np.asarray(state_a[0].count)
    count_b = np.asarray(state_b[0].count)
    assert count_a == 3 and count_b == 1
