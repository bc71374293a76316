"""Run ``__graft_entry__.dryrun_multichip(N)`` in a subprocess and commit
the outcome as a results/ artifact (VERDICT r4 next-round #4a: "dryrun
green at n_devices=32 — and record it").

Usage: python scripts/record_dryrun.py [N ...]   (default: 8 32)

Writes results/dryrun_multichip.json: one record per N with ok/rc/wall
seconds.  Subprocess per N because the virtual device count is fixed at
backend init.  The record is of the VIRTUAL-device structure check, so
each child is pinned to the CPU: on a machine with chips it neither
takes one nor reports a chip result under this file's "cpu" label (for
the real-chip dry run call ``dryrun_multichip`` directly there).  This
parent never imports jax.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "results", "dryrun_multichip.json")


def run_one(n: int) -> dict:
    # O0: the dry run checks sharding/collective structure, not codegen
    # quality, and O0 halves XLA:CPU compile time.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_backend_optimization_level=0")
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, "-c",
         f"import __graft_entry__ as g; g.dryrun_multichip({n}); print('OK')"],
        capture_output=True, text=True, timeout=1800, env=env, cwd=REPO,
    )
    wall = time.perf_counter() - t0
    ok = r.returncode == 0 and "OK" in r.stdout
    rec = {"n_devices": n, "ok": ok, "rc": r.returncode,
           "wall_seconds": round(wall, 1),
           "meshes": "1-D clients + 3-D (clients, seq, model) MoE-BERT"
                     if n % 4 == 0 else "1-D clients (+2-D if even)"}
    if not ok:
        rec["tail"] = (r.stdout + r.stderr)[-1000:]
    print(json.dumps(rec))
    return rec


def main() -> None:
    ns = [int(a) for a in sys.argv[1:]] or [8, 32]
    records = []
    for n in ns:
        records.append(run_one(n))
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    payload = {"recorded_unix": int(time.time()),
               "platform": "cpu (virtual devices; "
                           "xla_force_host_platform_device_count)",
               "runs": records}
    with open(OUT, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"wrote {OUT}")
    if not all(r["ok"] for r in records):
        sys.exit(1)


if __name__ == "__main__":
    main()
