"""``ops/mosaic.py::interpret``: whether a Mosaic call is interpreted is a
function of the device the trace is for (``jax.default_device``, else the
process's first), asked in one place, and every cache a trace is kept in
keys that state. So in one process and at one shape a trace for the CPU,
one for a TPU and one for the CPU again each get their own answer, through
the module-level ``jax.jit``s (``_gmm``, ``tgmm``, the taps' calls) and the
shape-keyed ``jax.checkpoint``s of the models (``blocks.experts_of``,
``kimi_linear._mixer``) alike; at the parent the second was served the
first's (``ROADMAP.md`` D17). Nothing runs on a TPU here: a trace needs
none."""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from jaxpr_tools import equations
from ps_tpu.models import kimi_linear
from ps_tpu.models.blocks import experts_of
from ps_tpu.ops import flash_attention, moe
from ps_tpu.ops.gated_conv import conv_silu_kernel
from ps_tpu.ops.gated_conv import path as taps_path
from ps_tpu.ops.grouped_matmul import gmm
from ps_tpu.ops.kda import kda
from ps_tpu.ops.kda import path as kda_path


def _arg(*shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _gmm_and_its_gradients():
    sizes = _arg(4, dtype=jnp.int32)

    def run(rows, stack, sizes, g):
        out, pull = jax.vjp(lambda rows, stack: gmm(rows, stack, sizes),
                            rows, stack)
        return out, pull(g)

    return run, (_arg(256, 128), _arg(4, 128, 128), sizes, _arg(256, 128)), 3


def _kda():
    wide = (1, 128, 2, 128)
    args = (_arg(*wide), _arg(*wide), _arg(*wide), _arg(*wide),
            _arg(*wide[:3]))
    assert kda_path(*args[:3], 64) == "kernel"
    return jax.grad(lambda *a: jnp.sum(kda(*a)), argnums=(0, 1, 2)), args, 2


def _flash_attention():
    q = _arg(1, 256, 2, 64)
    return jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True)), argnums=(0, 1, 2)), (q,) * 3, 2


def _conv_silu_kernel():
    x, w = _arg(1, 4096, 128), _arg(128, 4)
    assert taps_path(x, w) == "kernel"
    return jax.grad(lambda x, w: jnp.sum(conv_silu_kernel(x, w)),
                    argnums=(0, 1)), (x, w), 2


def _experts_of():
    tokens, width, inner, held, experts, picks = 256, 128, 128, 4, 8, 2

    def loss(x, router, gate, up, down):
        routing = moe.route(x, router, picks, renormalize=True,
                            scoring="sigmoid", held=(0, held))
        return jnp.sum(experts_of(x, gate, up, down, routing))

    args = (_arg(tokens, width), _arg(width, experts),
            _arg(held, width, inner), _arg(held, width, inner),
            _arg(held, inner, width))
    return jax.grad(loss, argnums=(0, 2, 3, 4)), args, 9


def _mixer():
    heads, width, rank, tokens = 2, 128, 16, (1, 4096)
    wide = heads * width
    projected = (_arg(*tokens, wide),) * 3 + (_arg(*tokens, rank),) * 2 \
        + (_arg(*tokens, heads),)
    weights = {**{f"{n}_conv": _arg(wide, 4) for n in "qkv"},
               "f_b": {"kernel": _arg(rank, wide)},
               "g_b": {"kernel": _arg(rank, wide)}, "dt_bias": _arg(wide),
               "A_log": _arg(heads), "out_norm": {"scale": _arg(width)}}
    assert taps_path(projected[0], weights["q_conv"]) == "kernel"
    return jax.grad(lambda p, w: jnp.sum(
        kimi_linear._mixer(p, w, heads, 1e-5)), argnums=(0, 1)), \
        (projected, weights), 2 + 6


#: the four kernel families, each with its gradients, and the two
#: ``jax.checkpoint``s of the models that hold some of them: what to trace,
#: at what, and the ``pallas_call``s at least
TRACED = {"gmm": _gmm_and_its_gradients, "kda": _kda,
          "flash_attention": _flash_attention,
          "conv_silu_kernel": _conv_silu_kernel,
          "blocks.experts_of": _experts_of, "kimi_linear._mixer": _mixer}


def _interpreted(fn, args):
    """``interpret`` of every ``pallas_call`` in ``fn``'s trace at ``args``."""
    return [bool(eqn.params["interpret"])
            for eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("name", sorted(TRACED))
def test_a_trace_is_for_the_device_it_was_made_under(name):
    fn, args, at_least = TRACED[name]()
    here = _interpreted(fn, args)
    assert len(here) >= at_least and all(here)
    with jax.default_device("tpu"):
        assert _interpreted(fn, args) == [False] * len(here)
    with jax.default_device(jax.devices()[0]):
        assert _interpreted(fn, args) == here
    assert _interpreted(fn, args) == here


def test_the_process_is_asked_in_one_place():
    """Under ``ps_tpu/ops/`` and ``ps_tpu/models/`` the text ``jax.devices(``
    stands in ``ops/mosaic.py`` alone, and no function signature there, nor
    anything under ``models/``, has a word of ``interpret`` but the kernels'
    own inner functions (static arguments, filled from the one place)."""
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "ps_tpu")
    asks, in_models = [], []
    for folder in ("ops", "models"):
        for file in sorted(os.listdir(os.path.join(root, folder))):
            if not file.endswith(".py"):
                continue
            with open(os.path.join(root, folder, file)) as f:
                text = f.read()
            if "jax.devices(" in text:
                asks.append(f"{folder}/{file}")
            if folder == "models" and "interpret" in text:
                in_models.append(file)
            public = re.findall(r"^def ([a-z]\w*)\(([^)]*)\)", text, re.M)
            taking = [fn for fn, params in public if "interpret" in params
                      and fn not in ("forward", "backward", "scalar_forward",
                                     "scalar_backward")]
            assert not taking, (file, taking)
    assert asks == ["ops/mosaic.py"]
    assert not in_models
