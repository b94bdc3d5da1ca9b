"""The plain reference that a configuration names.

A configuration file names, under ``reference``, the module that states its
architecture, as a path from the root of the checkout
(``"perfbench/references/cnn.py"``).  The harness loads it by that path, as
it loads ``metrics/<metric>.py``, and everything that depends on the
architecture goes through it; so the benchmark side of a new architecture
is new files: its configuration, its reference module and its cells.  A
reference module imports nothing of the program under test and gives:

``PRECISIONS``
    the precisions ``forward`` takes: ``highest`` (the reference), and the
    controls one precision down, ``high`` and ``bfloat16``.
``conv_table(config)``
    every conv in forward order, as dicts with ``name``, ``out``, ``k``,
    ``stride``, ``pad``, ``sparsity``, the input's ``c``, ``h``, ``w``, the
    output's ``e``, ``f``, ``res`` (a shortcut is added to its output) and
    optionally ``groups`` (1 where absent): what ``perfbench.counts`` and
    the kernel rooflines count, and what ``perfbench.system`` checks the
    program's lowered network against.
``fc_layers(config)``
    the FC layers in program order, as dicts with ``name``, ``in_f``,
    ``out_f`` and ``relu``; they run dense and count as such.
``make_weights(config, seed)``
    the conv weights drawn from the seed on the device, ``{name: (w, b)}``,
    w of shape (M, C/groups, K, K).
``fc_weights(config, fc_seed)``
    the FC matrices, (in_f, out_f) each, in program order.
``forward(config, weights, fc_ws, x, precision)``
    the logits (N, classes) of images ``x`` (N, C, H, W); traceable.
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys
from typing import Any, Dict

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(config: Dict[str, Any]):
    """The reference module the configuration names, imported once."""
    path = os.path.normpath(config["reference"])
    if os.path.isabs(path) or path.startswith(".."):
        raise ValueError(f"reference {config['reference']!r} is not a path "
                         "inside the checkout")
    name = "perfbench_reference_" + re.sub(r"\W", "_", path)
    mod = sys.modules.get(name)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(ROOT, path))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return mod
