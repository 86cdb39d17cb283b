"""Finding a part of the benchmark by its name.

Every part that belongs to one configuration, traffic mix, signature
scheme, server, traffic generator or per-layer metric is a file of its own
under ``benchmark/<kind>/``, found by the name that ``BENCHMARK.json`` or a
data file gives it:

- ``configs/<config>.json``: a deployment's sizes and guarantees; it may
  name a ``server`` (default ``bridge``);
- ``servers/<server>.py``: ``build(cfg, mix, scheme, fault)``, the system
  under test as the configuration deploys it;
- ``traffic/<mix>.json``: a mix's parameters; it names its ``signature``
  scheme and may name a ``generator`` (default: ``benchmark.traffic``);
- ``traffic/<generator>.py``: a ``Plan`` with the default's interface;
- ``schemes/<scheme>.py``: how voters sign and how the reference verifies;
- ``metrics/<metric>.py``: ``read(ctx)``, one per-layer metric.

A later PR adds one as a new file and an entry that names it, and edits no
file that is there.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


@functools.lru_cache(maxsize=None)
def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"bench: no {kind} module {name!r} at {path}")
    tag = "".join(c if c.isalnum() else "_" for c in f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location("bench_" + tag, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
