"""What the tests read off a traced program."""

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


def layers_keep_the_flash_residuals_alone(monkeypatch, model, loss_args,
                                          attn, attention_layers):
    """``model``'s ``_layer`` against the same layer under a ``jax.checkpoint``
    without a policy (monkeypatched in), on the loss's gradient at
    ``loss_args = (config, params, batch, bias)``: with 'flash' three kernel
    calls an attention layer where the policy-less one holds four, with
    'full' the same trace; either way the same loss and gradients, to the
    bit."""
    cfg, *args = loss_args

    def trace_and_run():
        fn = jax.value_and_grad(model.make_loss_fn(cfg, attn=attn),
                                has_aux=True)
        jaxpr = jax.make_jaxpr(fn)(*args)
        return (re.sub(r"0x[0-9a-f]+|policy=.*", "", str(jaxpr)),
                flash_calls(jaxpr.jaxpr),
                jax.jit(fn)(*args))

    text, calls, ((loss, _), grads) = trace_and_run()
    monkeypatch.setattr(model, "_layer", jax.checkpoint(
        model._layer.__wrapped__, static_argnums=(3, 4, 5)))
    plain_text, plain_calls, ((plain_loss, _), plain_grads) = \
        trace_and_run()
    flash = attn == "flash"
    assert calls == attention_layers * 3 * flash
    assert plain_calls == attention_layers * 4 * flash
    assert (text == plain_text) == (not flash)
    assert float(loss) == float(plain_loss)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(plain_grads)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))
