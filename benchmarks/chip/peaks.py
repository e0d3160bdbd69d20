"""The table of peaks, keyed by ``device_kind``. A device that is not in
the table is an error, never a default: a share of an unknown peak is
not a number."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind, path=_PATH):
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in {path}: add its "
            f"published peaks with their source (known: {sorted(table)})")
    return table[device_kind]
