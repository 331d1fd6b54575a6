"""The table of published peaks, `peaks.json`, keyed by `device_kind`."""

from __future__ import annotations

import json
import os
from typing import Any, Dict

TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def lookup(device_kind: str) -> Dict[str, Any]:
    """The peaks of one chip. A device that is not in the table is an
    error, not a default: a share of an unknown peak means nothing."""
    with open(TABLE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"perfbench/peaks.json has no device_kind {device_kind!r} "
            f"(it has {sorted(table)}); add it with its source")
    return table[device_kind]
