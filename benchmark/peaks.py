"""The table of peaks, keyed by the device kind JAX reports."""

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(kind: str) -> dict:
    with open(PATH) as f:
        kinds = json.load(f)["kinds"]
    if kind not in kinds:
        raise UnknownDevice(f"no peaks for device kind {kind!r}; "
                            f"benchmark/peaks.json knows {sorted(kinds)}")
    return kinds[kind]
