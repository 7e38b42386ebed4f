#!/usr/bin/env python3
"""Two one-chip serving replicas behind the HTTP transport, on a multi-chip
TPU host — the one-process-per-chip proof chip_smoke.py cannot give (it is
one process, and holds every chip).

    python3 tools/chip_fleet.py                     # from the repo root

This parent never initialises a JAX backend: `ProcReplicaPool` counts the
chips without one, pins each spawned replica to its own chip, and the
replicas build and warm chip_smoke.py's full-width resnet50 `Engine` (224
px, buckets 1 and 4) from a seed. Requests enter through `Transport` (HTTP) and fan out
round-robin, so both replicas answer; the same image must get the same
logits from either chip. Exits non-zero on any failed check or when the
host has fewer chips than replicas; the last stdout line is one JSON object.
"""
from __future__ import annotations

import glob
import http.client
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chip_smoke import resnet50_engine  # noqa: E402  (imports no jax)

OUT_DIR = os.path.join("chiprun_out", "chip_fleet")
REPLICAS = 2


def main() -> int:
    import numpy as np

    from deep_vision_tpu.core import backend
    from deep_vision_tpu.serve.procpool import READY_SUFFIX, ProcReplicaPool
    from deep_vision_tpu.serve.transport import Transport

    chips = backend.local_tpu_chips()
    print(f"chip_fleet: {chips} TPU chip(s) counted, backend initialised in "
          f"this process: {backend.backend_initialized()}", flush=True)
    if chips < REPLICAS:
        print(f"chip_fleet: needs {REPLICAS} TPU chips, found {chips}. "
              "No result.", file=sys.stderr)
        return 2
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    pool = ProcReplicaPool(resnet50_engine, replicas=REPLICAS,
                           run_dir=OUT_DIR,
                           excache_dir=os.path.join(OUT_DIR, "excache"),
                           ready_timeout_s=300.0, request_timeout_s=120.0)
    t0 = time.perf_counter()
    pool.start()
    transport = Transport(pool).start()
    try:
        ready_s = time.perf_counter() - t0
        assert not backend.backend_initialized(), \
            "the parent initialised a backend"
        replicas = {}
        for path in sorted(glob.glob(os.path.join(OUT_DIR,
                                                  "*" + READY_SUFFIX))):
            with open(path) as f:
                rec = json.load(f)
            replicas[rec["rid"]] = {"pid": rec["pid"],
                                    "device": rec["device"],
                                    "warmup": rec["warmup"]}
        assert len(replicas) == REPLICAS, replicas
        for rid, rec in replicas.items():  # one chip each, and only one
            assert rec["device"]["platform"] == "tpu" \
                and rec["device"]["count"] == 1, (rid, rec)
        image = np.random.RandomState(0).rand(112, 112, 12).astype(np.float32)
        body = json.dumps({"image": image.tolist()})
        answers, latencies = [], []
        for i in range(2 * REPLICAS + 2):
            conn = http.client.HTTPConnection("127.0.0.1", transport.port,
                                              timeout=180)
            t1 = time.perf_counter()
            conn.request("POST", "/v1/resnet50", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = json.loads(resp.read())
            latencies.append(round((time.perf_counter() - t1) * 1e3, 1))
            conn.close()
            assert resp.status == 200, (i, resp.status, str(payload)[:300])
            logits = np.asarray(payload["outputs"]["logits"], np.float32)
            assert logits.shape == (1000,) and np.isfinite(logits).all()
            answers.append(logits)
        spread = max(float(np.abs(a - answers[0]).max()) for a in answers)
        assert spread <= 1e-3 * float(np.abs(answers[0]).max()), spread
    finally:
        transport.close()
        summary = pool.drain("close")
        # serialized executables: too big to be worth bringing back
        shutil.rmtree(os.path.join(OUT_DIR, "excache"), ignore_errors=True)
    served = {rid: s.completed for rid, s in pool._slots.items()}
    assert all(n > 0 for n in served.values()), served
    assert summary["outcome"] == "flushed", summary
    result = {"ok": True, "chips": chips, "replicas": replicas,
              "ready_s": round(ready_s, 1), "served": served,
              "http_latency_ms": latencies, "max_logit_spread": spread,
              "ledger": pool.ledger()}
    with open(os.path.join(OUT_DIR, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
