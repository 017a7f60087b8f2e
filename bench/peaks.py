"""Published peaks of one chip, keyed by JAX's ``device_kind``."""
from __future__ import annotations

from pathlib import Path

from bench.spec import load_json

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    table = load_json(path)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {path.name}")
    return table[device_kind]
