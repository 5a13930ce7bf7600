"""TPU resource discovery for Spark's resource scheduling.

The reference relies on Spark GPU scheduling: a discovery script announces
each executor's GPUs and tasks read their assignment from
``TaskContext.resources()("gpu")`` (reference README.md:108-113,
RapidsRowMatrix.scala:125-126). Spark's discovery protocol is generic over
resource names: the script prints one JSON object
``{"name": <resource>, "addresses": [...]}`` on stdout.

``discovery_payload()`` probes TPUs on this host (JAX device enumeration,
falling back to the libtpu device files) and returns that JSON;
``write_discovery_script`` materializes a self-contained shell script for
``spark.worker.resource.tpu.discoveryScript``.
"""

from __future__ import annotations

import glob
import json
import os
import stat
from typing import List

RESOURCE_NAME = "tpu"

_SCRIPT = """#!/usr/bin/env bash
# TPU discovery script for Spark resource scheduling
# (spark.worker.resource.tpu.discoveryScript). Prints
# {"name": "tpu", "addresses": [...]} per Spark's discovery protocol.
exec python3 -m spark_rapids_ml_tpu.spark.discovery
"""


def _probe_device_files() -> List[str]:
    """Enumerate TPU chips via their device files (no jax init needed).

    Only /dev/accel* is trusted: VFIO group nodes are not TPU-specific
    (GPU passthrough creates them too, and /dev/vfio/vfio is a control
    node, not a device), so they are not counted."""
    paths = sorted(glob.glob("/dev/accel[0-9]*"))
    return [str(i) for i in range(len(paths))]


def _probe_jax() -> List[str]:
    try:
        import jax

        # JAX_PLATFORMS scopes discovery (e.g. "cpu" on non-TPU workers);
        # JAX reads it itself.
        return [str(d.id) for d in jax.devices() if d.platform == "tpu"]
    except Exception:  # noqa: BLE001 - discovery must never crash the worker
        return []


def discovery_payload() -> dict:
    """The JSON object Spark's discovery protocol expects on stdout."""
    addresses = _probe_device_files() or _probe_jax()
    return {"name": RESOURCE_NAME, "addresses": addresses}


def write_discovery_script(path: str) -> str:
    """Write the executable discovery script; returns the path."""
    with open(path, "w") as f:
        f.write(_SCRIPT)
    os.chmod(path, os.stat(path).st_mode | stat.S_IXUSR | stat.S_IXGRP | stat.S_IXOTH)
    return path


if __name__ == "__main__":
    print(json.dumps(discovery_payload()))
