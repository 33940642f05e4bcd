"""The names ``layer_metrics/decoder.py``'s metrics had while each model's
cell brought a reader of its own: ``moe.*``, ``lfm2.*``, ``kimi.*``,
``nemo.*``, ``trinity.*``, ``mellum.*``. ``BENCHMARK.json`` still lists 74
of the 87: in PR 67 the six ``<p>.mfu`` became ``step.mfu`` and the six
``<p>.flash_roofline`` / ``<p>.full_flash_roofline`` ``kernel.flash_roofline``
(names the manifest had for the newer cells already), and
``trinity.window_live_step_share``, a constant of a grid the windowed call
lost in PR 53, left. The six modules are views: their
old keys read into the one set, the one reader's result given under their old
names, nothing computed here. They go, with this file, when the manifest
lists ``decoder.*`` in their place: since PR 52 no test holds the older names
or imports a view, but ``tests/test_granite_h.py``, ``test_qwen3_next.py``,
``test_ouro.py`` and ``test_phi4flash.py`` hold the manifest at 128 names
and three cells' lists at two names, and ``tests/test_joyai.py`` its cell in
none, so the rest of the swap (128 -> 85) waits for a PR that may edit them
(PERF.md section 7, row 0).
"""

from __future__ import annotations

from benchmark.layer_metrics import decoder

#: the custom calls XLA:TPU made of ``jax.lax.ragged_dot`` until PR 47, by
#: the start of their own instruction name: they carried no scope. No cell's
#: step has one any more; the hand-made results of tests/test_phases.py do
GROUPED_MATMUL = "%ragged-dot"
#: older fact -> the one set's, where dropping the prefix does not give it
KEYS = {"full_flash_flops": "flash_flops", "full_flash_bytes": "flash_bytes",
        "flops_per_step": "dense_flops_per_step",
        "step_flops": "dense_flops_per_step",
        "expert_flops_per_step": "flops_per_pair"}


def names_of(prefix: str, listed: tuple, renamed=()) -> dict:
    """The one reader's name -> the older one, for each of ``listed``: under
    the reader's own prefix but for those in ``renamed``."""
    return {new: dict(renamed).get(new, f"{prefix}.{new.split('.', 1)[1]}")
            for new in listed}


def make(prefix: str, scopes: tuple, listed: tuple, renamed=()):
    """``(SCOPE_METRICS, scope_of, scope_times, read)`` of one older reader:
    ``scopes`` the family's (``phases.py``'s tuple, in its order), ``listed``
    the one reader's names of the metrics it listed."""
    names = names_of(prefix, listed, renamed)

    def scope_of(own: str, op_name: str):
        if own.startswith(GROUPED_MATMUL):
            return decoder.MOE_EXPERT
        return decoder.scope_of(own, op_name, scopes)

    def keys(old: dict) -> dict:
        cut = {k[len(prefix) + 1:] if k.startswith(prefix + "_") else k: v
               for k, v in (old or {}).items()}
        new = {KEYS.get(k, k): v for k, v in cut.items()}
        if "expert_flops_per_step" in cut:   # the step's experts as one pair
            new["live_pairs_per_step"] = 1.0
        return new

    def current(r: dict) -> dict:
        """``r`` with its facts and counters under the one set of keys."""
        return {"trace": None, "traced_steps": 0, "peaks": {}, "chips": 1,
                **r, "facts": keys(r.get("facts")),
                "counters": keys(r.get("counters"))}

    def older(out: dict) -> dict:
        return {names[k]: v for k, v in out.items() if k in names}

    def scope_times(r: dict, op_names: dict) -> dict:
        return older(decoder.scope_times(current(r), op_names, scope_of))

    def read(r: dict) -> dict:
        return older(decoder.read(current(r), scope_of))

    scope_metrics = {s: names[decoder.METRICS[s]] for s in scopes
                     if decoder.METRICS[s] in names}
    return scope_metrics, scope_of, scope_times, read
