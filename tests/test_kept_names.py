"""What a layer's ``jax.checkpoint`` keeps by name, read off the traced
gradient: ``ops/moe.py::ROUTE_KEPT`` is the identity wherever no policy lists
it (the four models that share ``route`` and do not; SDAR's names under the
policy it had before it listed them) and takes the router's product, its
``top_k`` and its two sorts out of the recomputation where one does. The
four models whose policies list it have their own cases in
``test_nemotron_h.py``, ``test_trinity.py``, ``test_joyai.py`` and
``test_sdar.py``."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jaxpr_tools import (checkpoint_names, equations, highest_products,
                         primitives, traced_and_run)
from ps_tpu.models import blocks, sdar
from ps_tpu.ops import moe
from ps_tpu.ops.flash_attention import KEPT

#: the models that call ``route`` under no policy that lists its names, each
#: with the ``name`` equations its gradient holds: four a call of ``route``,
#: counted once where it runs under no checkpoint (OLMoE's layers in small:
#: two; LFM2's and Kimi's expert layers: four each) and twice, forward and
#: recomputation, where a layer's policy lists the flash call's residuals
#: alone (Mellum's four layers, on a mesh of four). SDAR's three layers list
#: them since PR 71: its case puts them back under the policy they had
#: before, ``flash_attention.KEPT`` alone, where ``route``'s four names and
#: the six of ``sdar.PRODUCTS_KEPT`` are each borne twice a layer
SHARE_ROUTE = {"olmoe": 4 * 2, "lfm2": 4 * 4, "kimi_linear": 4 * 4,
               "mellum": 2 * 4 * 4, "sdar": 2 * (4 + 6) * 3}


def _traced_gradient(name, **kw):
    tests = importlib.import_module(f"test_{name}")
    model = importlib.import_module(f"ps_tpu.models.{name}")
    _, cfg, *args = tests._setup()
    if name == "mellum":
        kw["mesh"] = tests._mesh()
    fn = jax.value_and_grad(model.make_loss_fn(cfg, **kw), has_aux=True)
    return jax.make_jaxpr(fn)(*args).jaxpr


@pytest.fixture
def without_names(monkeypatch):
    """Call it to take ``route``'s names out, and SDAR's own. jax keeps a
    checkpointed layer's trace by the layer's identity, so the traces made
    before are dropped, and those made without names when the test is
    over."""
    def patch():
        for module in (moe, sdar):
            monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
        jax.clear_caches()

    yield patch
    monkeypatch.undo()
    jax.clear_caches()


@pytest.mark.parametrize("name", sorted(SHARE_ROUTE))
def test_routes_names_are_the_identity_where_no_policy_lists_them(
        name, without_names, monkeypatch):
    """The loss's gradient with ``route``'s names is, equation for equation,
    the one without them (``checkpoint_name`` patched out, which is the
    parent's ``route``) but for the ``name`` equations themselves: as many
    ``dot_general``s, ``top_k``s and sorts, in the same order. SDAR's layer
    under a policy that lists the flash call's residuals alone says the same
    of every name ``models/sdar.py`` gives."""
    names = set(moe.ROUTE_KEPT)
    if name == "sdar":
        names |= set(sdar.PRODUCTS_KEPT)
        monkeypatch.setattr(sdar, "_layer", jax.checkpoint(
            sdar._layer.__wrapped__, static_argnums=(2, 3),
            policy=jax.checkpoint_policies.save_only_these_names(*KEPT)))
    named = _traced_gradient(name)
    assert checkpoint_names(named) == names
    without_names()
    plain = primitives(_traced_gradient(name))
    assert "name" not in plain and plain.count("top_k") > 0
    listed = primitives(named)
    assert listed.count("name") == SHARE_ROUTE[name]
    assert [p for p in listed if p != "name"] == plain


@pytest.mark.parametrize("name", ["sdar"])
def test_a_gradient_bears_the_names_its_layers_policy_lists_and_no_other(
        name):
    """With 'flash' the names in the loss's gradient are exactly those of the
    layer's policy: the flash call's residuals, ``route``'s four and the
    file's ``PRODUCTS_KEPT``. A name that a traced run showed to return
    nothing does not stay in the model (``PERF.md`` section 6, PRs 51 and
    71)."""
    model = importlib.import_module(f"ps_tpu.models.{name}")
    assert checkpoint_names(_traced_gradient(name, attn="flash")) \
        == {*KEPT, *moe.ROUTE_KEPT, *model.PRODUCTS_KEPT}


@pytest.mark.parametrize("held", [None, (4, 2)], ids=["all", "share"])
def test_a_policy_that_lists_routes_names_routes_once(held):
    """An expert layer under a ``jax.checkpoint`` whose policy is
    ``ROUTE_KEPT``, against the same under one without a policy: the
    gradient holds one ``top_k`` where the other holds two, one router
    product at ``HIGHEST`` fewer (the forward's once, and the two of its
    backward), two sorts fewer (``order`` and ``inverse``), and the same
    bits."""
    tokens, width, experts, top_k, hidden = 64, 32, 16, 6, 24
    rng = np.random.default_rng(0)
    x, router, w1, w2 = (
        jnp.asarray(rng.normal(size=shape), jnp.float32)
        for shape in ((tokens, width), (width, experts),
                      (held[1] if held else experts, width, hidden),
                      (held[1] if held else experts, hidden, width)))

    def layer(x, router, w1, w2):
        routing = moe.route(x, router, top_k, renormalize=True,
                            scoring="sigmoid", held=held)
        out = moe.over_windows(blocks.LIVE_ROWS, routing, x, w1, None, w2)
        return x + out

    def gradient(**policy):
        fn = jax.grad(lambda *a: jnp.sum(jax.checkpoint(layer, **policy)(*a)),
                      argnums=(0, 1, 2, 3))
        return traced_and_run(fn, x, router, w1, w2)

    kept, grads = gradient(
        policy=jax.checkpoint_policies.save_only_these_names(*moe.ROUTE_KEPT))
    plain, plain_grads = gradient()
    assert primitives(kept).count("top_k") == 1
    assert primitives(plain).count("top_k") == 2
    assert highest_products(plain) - highest_products(kept) == 1
    router_products = [e for e in equations(kept)
                       if e.primitive.name == "dot_general"
                       and {v.aval.shape for v in e.invars} & {router.shape}]
    assert len(router_products) == 2      # the forward's and x's cotangent
    assert primitives(plain).count("sort") - primitives(kept).count("sort") \
        == 2
    for g, w in zip(grads, plain_grads):
        assert float(jnp.max(jnp.abs(g))) > 0
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# -- Granite-4.0-H's policy: the flash call's residuals, four products of five --

@pytest.mark.parametrize("attn", ["full", "flash"])
def test_granites_layers_keep_what_their_policy_lists(monkeypatch, attn):
    """``models/granite_h.py::_layer`` names five values (a mixer's in
    projection, the SwiGLU's ``x W_in``, the attention's q, k and v) and its
    policy lists four of them beside the flash call's residuals: against a
    ``jax.checkpoint`` without a policy the loss is the same bits and every
    gradient the same to a few f32 roundoffs; the gradient holds seven matrix
    products fewer: the one attention layer's q, k and v and the four layers'
    ``x W_in`` ('mamba_in' is made again: the compiled peak has no room for
    it; the scan's output bears no name since PR 59: in the cell the scan is
    two Mosaic calls whose forward runs again for the states the backward
    reads, and gives the output with them); with 'flash', three kernel calls
    where the policy-less one holds four."""
    from ps_tpu.models import granite_h
    import test_granite_h

    _, cfg, params, batch = test_granite_h._setup()
    assert granite_h.PRODUCTS_KEPT == ("attn_q", "attn_k", "attn_v",
                                       "ffn_in")

    def trace_and_run():
        jaxpr, out = traced_and_run(jax.value_and_grad(
            granite_h.make_loss_fn(cfg, attn=attn)), params, batch)
        calls = sum(e.primitive.name == "pallas_call"
                    for e in equations(jaxpr))
        return (checkpoint_names(jaxpr),
                primitives(jaxpr).count("dot_general"), calls, out)

    names, products, calls, (loss, grads) = trace_and_run()
    assert names == {"mamba_in", *granite_h.PRODUCTS_KEPT,
                     *(KEPT if attn == "flash" else ())}
    monkeypatch.setattr(granite_h, "_layer", jax.checkpoint(
        granite_h._layer.__wrapped__, static_argnums=(2, 3, 4)))
    _, plain_products, plain_calls, (plain_loss, plain_grads) = \
        trace_and_run()
    flash = attn == "flash"
    assert (calls, plain_calls) == (3 * flash, 4 * flash)
    assert plain_products - products == 3 + 4
    assert float(loss) == float(plain_loss)
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(plain_grads)):
        assert float(jnp.max(jnp.abs(g - w))) \
            <= 1e-5 * float(jnp.max(jnp.abs(w)))
