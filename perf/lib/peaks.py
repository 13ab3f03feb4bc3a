"""The one table of chip peaks, keyed by ``device_kind`` as JAX
reports it. A kind that is not in the table is an error, never a
default: a roofline share against the wrong peak is a wrong number."""
import json
import os
from typing import Any, Dict

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'peaks.json')


def peaks_for(device_kind: str) -> Dict[str, Any]:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f'device_kind {device_kind!r} is not in {_PATH}; add its '
            f'published peaks with their source before measuring on it')
    return table[device_kind]
