"""What the tests read off a traced program."""

import hashlib
import re

import jax
import numpy as np


def equations(jaxpr):
    """``jaxpr``'s equations in order, those of the programs its equations
    hold included, but a ``pallas_call``'s kernel body."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(sub)


def primitives(jaxpr):
    """The names of ``jaxpr``'s primitives in order (``equations``); a
    ``pallas_call`` counts as one."""
    return [eqn.primitive.name for eqn in equations(jaxpr)]


def flash_calls(jaxpr):
    """How many ``pallas_call``s of ``jaxpr`` are the attention's: the
    grouped matmul's carry its kernels' names, the flash kernels none."""
    return sum(eqn.primitive.name == "pallas_call"
               and eqn.params["name"] is None for eqn in equations(jaxpr))


def checkpoint_names(jaxpr):
    """The set of names ``checkpoint_name`` gave inside ``jaxpr``."""
    return {eqn.params["name"] for eqn in equations(jaxpr)
            if eqn.primitive.name == "name"}


def highest_products(jaxpr):
    """How many ``dot_general``s of ``jaxpr`` run at ``Precision.HIGHEST``."""
    highest = jax.lax.Precision.HIGHEST
    return sum(eqn.primitive.name == "dot_general"
               and eqn.params["precision"] in (highest, (highest, highest))
               for eqn in equations(jaxpr))


def recomputed(jaxpr):
    """The equations of every differentiated ``jax.checkpoint`` of
    ``jaxpr``'s top level: a layer's recomputation and its backward pass, as
    the gradient holds them."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "remat2" and eqn.params["differentiated"]:
            yield from equations(eqn.params["jaxpr"])


def weight_products(eqns):
    """The shapes of the 2-D right operands of the ``dot_general``s among
    ``eqns`` that contract an activation's last axis with a weight's first:
    ``x @ W`` as a forward pass writes it. Neither cotangent of such a
    product has that form (``dy @ W.T`` contracts the weight's second axis,
    ``x.T @ dy`` the rows)."""
    shapes = []
    for eqn in eqns:
        if eqn.primitive.name != "dot_general":
            continue
        (lhs, rhs), _ = eqn.params["dimension_numbers"]
        x, w = (v.aval for v in eqn.invars)
        if w.ndim == 2 and tuple(rhs) == (0,) and tuple(lhs) == (x.ndim - 1,):
            shapes.append(w.shape)
    return shapes


def traced_and_run(fn, *args):
    """``fn``'s jaxpr at ``args`` and its result there, compiled without the
    backend's optimizations: XLA:CPU's fused kernels round a product and a
    sum once or twice by what happened to be fused around them, so two
    programs that compute the same values agree to the bit only without."""
    compiled = jax.jit(fn).lower(*args).compile(
        compiler_options={"xla_backend_optimization_level": 0})
    return jax.make_jaxpr(fn)(*args).jaxpr, compiled(*args)


def layers_keep_what_their_policy_lists(monkeypatch, model, loss_args, attn,
                                        attention_layers, fewer_products):
    """``model``'s ``_layer`` against the same layer under a ``jax.checkpoint``
    without a policy (monkeypatched in), on the loss's gradient at
    ``loss_args = (config, params, batch, bias)``: the same loss and
    gradients, to the bit (f32 on the CPU, where a kept value is the value
    its recomputation makes; ``traced_and_run``); with 'flash' three kernel
    calls an attention
    layer where the policy-less one holds four; and ``fewer_products``
    ``dot_general``s fewer, the matrix products whose outputs the policy
    lists by name and the recomputation therefore leaves out."""
    cfg, *args = loss_args

    def trace_and_run():
        jaxpr, out = traced_and_run(jax.value_and_grad(
            model.make_loss_fn(cfg, attn=attn), has_aux=True), *args)
        return primitives(jaxpr).count("dot_general"), flash_calls(jaxpr), out

    products, calls, ((loss, _), grads) = trace_and_run()
    monkeypatch.setattr(model, "_layer", jax.checkpoint(
        model._layer.__wrapped__, static_argnums=(3, 4, 5)))
    plain_products, plain_calls, ((plain_loss, _), plain_grads) = \
        trace_and_run()
    flash = attn == "flash"
    assert calls == attention_layers * 3 * flash
    assert plain_calls == attention_layers * 4 * flash
    assert plain_products - products == fewer_products
    assert float(loss) == float(plain_loss)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(plain_grads)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


def digest(fn, *args):
    """sha256 of ``fn``'s jaxpr at ``args`` as text, the addresses that a
    function's repr carries taken out: the same program gives the same
    digest in every process."""
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()
