"""Model families: one module per published ``model_type`` in ``bench/models``.

A configuration file carries the ``model_type`` of its published
``config.json``; ``load`` finds ``bench/models/<model_type>.py`` by that
name and nothing else.  A family module owns everything that depends on
the architecture, and provides:

* ``dims(cfg)``: the sizes the served tree is laid out in;
* ``init(cfg, key)``: the whole weight tree from one key, in the layout the
  program's serve step reads (``bench/core/weights.py`` jits it);
* ``forward(cfg, fp8, params, tokens, where)``: the plain float32
  reference forward, logits ``[len(where), vocab_size]`` at the positions
  ``where`` of one token sequence (``bench/reference.py`` jits it);
* ``program_settings(cfg)``: the fields of the program's ``ArchConfig``
  set from the file's published values;
* ``program_widths(cfg)``: the ``ArchConfig`` fields the program must run
  as the file states them;
* ``linear_flops_per_token(d)``, ``attn_flops(d, pairs)`` and
  ``attn_bytes(d, ctx, n)``: the work counts of ``bench/core/counts.py``,
  from the sizes ``d`` that ``dims`` gives.
"""
from __future__ import annotations

import importlib.util
import os
from types import ModuleType
from typing import Dict

DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "models")
API = ("dims", "init", "forward", "program_settings", "program_widths",
       "linear_flops_per_token", "attn_flops", "attn_bytes")

_loaded: Dict[str, ModuleType] = {}


def load(cfg: dict) -> ModuleType:
    """The family module of a configuration (or of the sizes ``dims``
    gives, which carry the ``model_type`` too)."""
    name = cfg["model_type"]
    path = os.path.join(DIR, name + ".py")
    if path not in _loaded:
        if not os.path.isfile(path):
            raise SystemExit(
                f"no model family {name!r}: add bench/models/{name}.py "
                f"(bench/core/models.py lists what it provides)")
        sp = importlib.util.spec_from_file_location(f"bench_model_{name}",
                                                    path)
        mod = importlib.util.module_from_spec(sp)
        sp.loader.exec_module(mod)
        missing = [f for f in API if not callable(getattr(mod, f, None))]
        if missing:
            raise SystemExit(f"bench/models/{name}.py lacks {missing}")
        _loaded[path] = mod
    return _loaded[path]
