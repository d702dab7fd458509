"""The program surface the chip benchmark drives, and nothing else.

The harness touches the served path only through the names imported here:
the gateway (``build_gateway``, ``Event``), the dispatch types it returns
(``Dispatch``, ``Assignment``), the share runner (``ShareRunner``,
``place_nodes``, ``ShareResult`` and the shape constants), the model
config registry and the persistent compile cache switch. A change to the
served path keeps these names and their meaning, or the benchmark stops.

The program lives under ``src/`` of the checkout; this module puts it on
``sys.path``. In a directory that holds only the benchmark the import
fails, and the run exits nonzero.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(ROOT, "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.configs import get_config  # noqa: E402
from repro.core.requests import (Assignment, Dispatch,  # noqa: E402
                                 InferenceRequest)
from repro.core.resource_manager import Event  # noqa: E402
from repro.core.variants import VariantPool  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import (DECODE_STEPS, PROMPT_LEN,  # noqa: E402
                                SHARE_ITEMS, ShareResult, ShareRunner,
                                build_gateway, place_nodes)

__all__ = [
    "ROOT", "get_config", "Assignment", "Dispatch", "InferenceRequest",
    "Event", "VariantPool", "enable_compile_cache", "DECODE_STEPS",
    "PROMPT_LEN", "SHARE_ITEMS", "ShareResult", "ShareRunner",
    "build_gateway", "place_nodes",
]
