"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in ``peaks.json`` is an
error, never a default."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(kind: str, path: str = PATH) -> dict:
    with open(path) as f:
        table = json.load(f)
    try:
        return dict(table["devices"][kind])
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"{path}; add them with their source") from None
