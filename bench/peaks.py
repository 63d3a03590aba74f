"""The table of chip peaks (``peaks.json``), keyed by ``device_kind``."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class UnknownDevice(Exception):
    """The device is not a TPU listed in the peaks table."""


def peaks_for(platform: str, device_kind: str, path: str = PEAKS_FILE
              ) -> dict:
    with open(path) as f:
        table = json.load(f)["devices"]
    if platform != "tpu":
        raise UnknownDevice(f"platform {platform!r} is not a TPU")
    if device_kind not in table:
        raise UnknownDevice(f"device kind {device_kind!r} is not in "
                            f"{path}; add its published peaks there")
    return table[device_kind]
