"""Standalone fleet controller/client process for the gossip flagships.

Spawned by tests/test_gossip.py against daemons living in the PARENT
test process: this worker holds NO endpoint roster — it bootstraps
everything from the ONE seed address in argv, the way a fresh operator
box (or a supervisor-restarted controller) joins a running fleet. Two
modes:

* ``rollout <seed> <npz> <model> <version>`` — ``ModelFleet.from_seeds``
  then a v_old→v_new rollout using the ``v2.*`` arrays in the npz.
  With ``SRML_FAULT_PLAN=fleet.rollout:crash:...`` in the env this
  process dies abruptly (exit 17) at the chosen rollout-intent
  checkpoint — AFTER the phase's intent was gossiped, BEFORE its work
  ran: exactly the mid-rollout controller death the successor's
  ``resume_rollout`` must finish or abort. Prints ``DONE <json>`` when
  the plan lets it live.
* ``traffic <seed> <npz> <model> <count>`` — ``FleetClient.from_seeds``
  then routed transforms of the npz's ``q`` batch, each checked bitwise
  against its ``ref`` oracle; one ``OK <n>`` line per request
  (``count`` <= 0 loops forever — the parent SIGKILLs this mode
  mid-traffic and bootstraps a successor from a different seed).
"""

import json
import sys


def main() -> None:
    import jax

    # Tests run on the CPU: this worker must use the same backend as the
    # test session, whatever the host offers.
    jax.config.update("jax_platforms", "cpu")

    from spark_rapids_ml_tpu.utils.compile_cache import ensure_compile_cache

    ensure_compile_cache()

    import numpy as np

    mode, seed, npz_path, model = sys.argv[1:5]
    data = np.load(npz_path)

    if mode == "rollout":
        from spark_rapids_ml_tpu.serve.fleet import ModelFleet

        new_v = int(sys.argv[5])
        arrays = {
            k[len("v2."):]: data[k] for k in data.files
            if k.startswith("v2.")
        }
        with ModelFleet.from_seeds([seed]) as fleet:
            res = fleet.rollout(
                model, "pca", arrays, version=new_v, warm=False
            )
        print("DONE " + json.dumps(
            {"version": res["version"], "previous": res["previous"],
             "epoch": res["epoch"], "drained": res["drained"]}
        ), flush=True)
    elif mode == "traffic":
        from spark_rapids_ml_tpu.serve.router import FleetClient

        count = int(sys.argv[5])
        q, ref = data["q"], data["ref"]
        with FleetClient.from_seeds([seed]) as fc:
            n = 0
            while count <= 0 or n < count:
                out = fc.transform(model, q)
                got = np.asarray(out["output"])
                print(("OK" if np.array_equal(got, ref) else "MISMATCH")
                      + f" {n}", flush=True)
                n += 1
    else:
        raise SystemExit(f"unknown mode {mode!r}")


if __name__ == "__main__":
    main()
