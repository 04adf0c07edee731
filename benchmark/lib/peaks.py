"""Published peaks of each device, keyed by JAX's ``device_kind``
(benchmark/peaks.json). A device that is not in the table is an error,
never a default."""

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    with open(path) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise UnknownDevice("no published peaks for device kind %r in %s" % (device_kind, os.path.basename(path)))
    return table[device_kind]
