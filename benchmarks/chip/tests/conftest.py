"""Shared helpers of the yardstick's tests: the benchmark's directory on
``sys.path`` and configuration documents for the program's smoke configs
(the CPU stands in for the chip; no number here is a chip number)."""
from __future__ import annotations

import copy
import glob
import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import entry  # noqa: E402
from repro.configs import ARCH_NAMES, get_smoke_config  # noqa: E402


def _config_files() -> dict:
    """Each arch's configuration file: ``configs/<arch>.json`` where there
    is one, else the first by name that serves the arch."""
    files = {}
    for path in sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))):
        with open(path) as f:
            arch = json.load(f)["arch"]
        if os.path.basename(path) == f"{arch}.json" or arch not in files:
            files[arch] = path
    return files


CONFIG_FILES = _config_files()


def smoke_doc(arch: str, doc: dict | None = None) -> dict:
    """The configuration file of ``arch`` (or ``doc``) with the program's
    smoke sizes in place of the published ones, as its reference names
    them (``smoke_sizes``), and the smoke ladder under the keys of the
    file's own ladder."""
    smoke = get_smoke_config(arch)
    if doc is None:
        with open(CONFIG_FILES[arch]) as f:
            doc = json.load(f)
    doc = copy.deepcopy(doc)
    ref = importlib.import_module(f"reference.{doc['reference']}")
    doc.update(ref.smoke_sizes(doc, smoke))
    keys = [k for k in doc["ladder"][0] if k not in ("level", "accuracy")]
    ladder = []
    for v in entry.VariantPool(smoke).variants:
        sizes = ref.smoke_sizes(doc, v.config)
        ladder.append({"level": v.level, **{k: sizes[k] for k in keys},
                       "accuracy": v.accuracy})
    doc["ladder"] = ladder
    return doc


@pytest.fixture(params=sorted(a for a in CONFIG_FILES if a in ARCH_NAMES))
def arch(request):
    """Every arch a configuration file serves, once each."""
    return request.param
