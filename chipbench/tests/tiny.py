"""CPU-sized cells for the tests: the harness's runners, traffic and
comparison at qwen3 shapes small enough for a test run."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

DATA = Path(__file__).resolve().parent / "data"
CHIPBENCH = DATA.parent.parent


def cell(mix="packed", limits=None):
    cfg = json.loads((DATA / "qwen3-tiny-spmd.json").read_text())
    cfg["name"] = "qwen3-tiny-spmd"
    if limits is not None:
        cfg["check"]["limits"] = dict(limits)
    mix = json.loads((CHIPBENCH / "traffic" / f"{mix}.json").read_text())
    return SimpleNamespace(workload={"name": "tiny.spmd"}, cfg=cfg, mix=mix, chips=1,
                           end_to_end=[], per_layer=[])
