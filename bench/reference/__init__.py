"""Plain references of the configurations the benchmark runs.

A configuration file names its reference module under ``"reference"``
(``bench/reference/<name>.py``; ``model`` where the key is absent).  The
module owns everything the harness knows of the configuration's family:

- ``FIELDS``: per ``family``, a table from each file key to the program's
  ``ModelConfig`` attribute and what the training driver does with it
  (``SET``: apply the file's value; ``CHECK``: the program's value must
  equal the file's);
- ``dims(conf)``: the sizes and scalars the reference computes with;
- ``init_params(m, key)``, ``loss(m, params, tokens, targets, prec)``: the
  weights from the seed and the loss;
- ``leaf_norms(params)``: the norm of each leaf under the names the
  driver gives the program's leaves;
- ``layers_forward_per_token(c, context)``: the forward FLOPs of all the
  layers for one token (``bench/flops.py`` adds the head).
"""
from __future__ import annotations

import importlib
import re
from types import ModuleType
from typing import Callable, Dict, NamedTuple, Optional

SET, CHECK = "set", "check"
_NAME = re.compile(r"^[a-z][a-z0-9_]*$")


class Field(NamedTuple):
    """One row of a ``FIELDS`` table: the ``ModelConfig`` attribute, the
    action, and, for a size the file does not state (a head size), how to
    work it out from the file; otherwise the file's value under the key."""
    attr: str
    action: str
    derive: Optional[Callable[[Dict], object]] = None

    def value(self, c: Dict, key: str):
        return self.derive(c) if self.derive else c[key]


def load(conf: Dict) -> ModuleType:
    """The reference module a configuration file names."""
    name = conf.get("reference", "model")
    if not _NAME.match(name):
        raise ValueError(f"{conf.get('name')}: reference {name!r} is not a "
                         f"module name under bench/reference")
    return importlib.import_module("bench.reference." + name)
