"""The model a configuration names, found by name like a traffic driver.

A configuration file (``bench/configs/<config>.json``) names its model
under ``"model"``. Two modules under the checkout's ``bench/`` describe
it, and a new architecture is these two files and a configuration that
names them:

  bench/models/<model>.py     the system under test's side:
      program_config(cfg)               the program's ``ModelConfig``
      make_params(cfg, seed)            weights from the seed, on the
                                        device, in the program's layout
      matmul_params(cfg)                the model's counts (``counts.py``
      attention_flops(cfg, keys)        forwards to them; the skip term,
      decode_token_flops(cfg, position) readout and kernel calls are
      prefill_flops(cfg, length)        counted there for every model)

  bench/reference/<model>.py  the plain float32 reference, which imports
                              nothing of the program:
      hidden(params, cfg, tokens, precision)  (final hidden (T, D),
                                              block inputs (L, T, D))
      logits(params, cfg, y, precision)       final norm and head
      cfg_items(cfg)                          the numbers it reads,
                                              hashable for ``jit``
      sequence_logits(params, cfg, tokens, a, b, precision)

A process loads each module once, from the first root it is asked for
(the checkout's own ``bench/`` in a run); later lookups by the same name
return that module, so its jitted functions keep their compiled programs.
"""

from __future__ import annotations

import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(kind: str, name: str, root=None):
    key = f"bench_{kind}_{name}"
    if key not in sys.modules:
        path = os.path.join(root or os.path.dirname(HERE), "bench", kind,
                            f"{name}.py")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


def model(cfg: dict, root=None):
    """The module ``bench/models/<cfg["model"]>.py``."""
    return _load("models", cfg["model"], root)


def reference(cfg: dict, root=None):
    """The plain reference ``bench/reference/<cfg["model"]>.py``."""
    return _load("reference", cfg["model"], root)
