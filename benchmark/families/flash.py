"""The flash kernel's operations and HBM bytes from shapes: the one count
behind every ``*flash_roofline`` (``ps_tpu/ops/flash_attention.py``; no
family of its own, the families call it).

A step runs the kernel's calls once a layer: the forward, and a backward of
two Mosaic calls (dk / dv, then dq), or of one where one tile spans the
sequence and each K/V head serves one query head (BERT at 512). A matmul
over the scores of one head is ``2 x pairs x width`` operations, ``pairs``
the query-key pairs the mask lets that head see in one sequence
(``seen_pairs``): the square, the triangle, or a band of it.
"""

from __future__ import annotations

#: the matmuls of each call, as multiples of the key width and of the value
#: width. Forward: QK^T and PV. dk / dv: the scores, dP, dv and dk. dq: the
#: scores, dP and dq. The one backward call: the scores, dP, dv, dk and dq
FORWARD = (1, 1)
BACKWARD = {"two calls": (2 + 2, 2 + 1), "one call": (3, 2), None: (0, 0)}


def seen_pairs(seq, window=None, causal=True):
    """Query-key pairs one head of one sequence attends over: the square
    without a mask over positions; the triangle with its diagonal; or the
    band of ``window`` keys a row (itself and the ``window - 1`` before
    it), whose first rows see fewer."""
    if not causal:
        return seq * seq
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def cost(batch, heads, kv_heads, seq, qk_dim, v_dim, layers, pairs,
         backward="two calls", itemsize=2):
    """``(operations, HBM bytes)`` of the kernel's calls in one step of one
    chip, forward and ``backward`` (``"two calls"``, ``"one call"``, or
    ``None`` for a program whose backward is no kernel). Keys are
    ``qk_dim`` wide and values ``v_dim``; K and V are read at their own head
    count (the kernel's index map reads head ``h // group``: no repeated
    copy is made or counted). Bytes: the forward reads q, k, v and writes
    the output and the f32 logsumexp; dk / dv reads q, dO, k, v and the two
    f32 rows and writes dk and dv; dq reads the same and writes dq; the one
    call reads q, dO, k, v and the logsumexp and writes all three. Every
    call moves whole arrays whatever the mask skips."""
    qk_products, v_products = (f + b for f, b in zip(FORWARD,
                                                     BACKWARD[backward]))
    flops = layers * batch * heads * 2.0 * pairs * (
        qk_products * qk_dim + v_products * v_dim)
    q, o = qk_dim * itemsize * heads, v_dim * itemsize * heads  # dO as o
    k, v = qk_dim * itemsize * kv_heads, v_dim * itemsize * kv_heads
    row = 4 * heads                                   # one f32 a query
    per_token = q + k + v + o + row
    if backward == "two calls":
        per_token += (q + o + k + v + 2 * row + k + v) \
            + (q + o + k + v + 2 * row + q)
    elif backward == "one call":
        per_token += q + o + k + v + row + k + v + q
    return flops, float(layers * batch * seq * per_token)
