#!/usr/bin/env python3
"""The open-loop load generator: a child process that never imports jax,
so that it shares no interpreter lock with the engine.

    python3 benchmark/loadgen.py --schedule s.json --host H --port P \
        --t0 <time.monotonic() at which due_s = 0> --grace-s 60 --out r.json

``s.json`` is a list of requests ``{"index", "due_s", "prompt",
"max_tokens"}`` (``benchmark/traffic.py`` makes it).  Each request is
sent when it is due, whatever became of the earlier ones, on a thread of
its own, streamed, greedy.  For each the child records when it was due,
when it was sent, when each token arrived (``time.monotonic()``, one
clock for every process of the machine) and the token ids.  A request
that has not ended ``grace-s`` seconds after the last one was due is
recorded as failed (``timeout``).
"""

from __future__ import annotations

import argparse
import http.client
import json
import sys
import threading
import time


def one_request(host, port, req, t0, timeout_s, rec):
    body = json.dumps({"prompt": req["prompt"],
                       "max_tokens": req["max_tokens"],
                       "temperature": 0.0, "stream": True})
    conn = http.client.HTTPConnection(host, port, timeout=timeout_s)
    try:
        rec["sent_s"] = time.monotonic() - t0
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read(300).decode("utf-8", "replace")
            return
        while True:
            line = resp.readline()
            if not line:
                rec["error"] = "stream ended without [DONE]"
                return
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            now = time.monotonic() - t0
            data = line[6:]
            if data == b"[DONE]":
                rec["done"] = True
                resp.read()      # the stream's end, so the close is clean
                return
            msg = json.loads(data)
            if "error" in msg:
                rec["error"] = json.dumps(msg["error"])
                return
            choice = msg["choices"][0]
            rec["token_s"].append(now)
            rec["tokens"].append(choice["token_id"])
            if choice.get("finish_reason"):
                rec["finish_reason"] = choice["finish_reason"]
    except Exception as e:  # noqa: BLE001 - recorded, the request failed
        rec["error"] = f"{type(e).__name__}: {e}"
    finally:
        conn.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--schedule", required=True)
    ap.add_argument("--host", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--t0", type=float, required=True)
    ap.add_argument("--grace-s", type=float, default=60.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    with open(args.schedule) as f:
        schedule = sorted(json.load(f), key=lambda r: r["due_s"])
    last_due = schedule[-1]["due_s"] if schedule else 0.0
    deadline = args.t0 + last_due + args.grace_s
    records, threads = [], []
    for req in schedule:
        wait = args.t0 + req["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        rec = {"index": req["index"], "due_s": req["due_s"], "sent_s": None,
               "status": None, "token_s": [], "tokens": [], "done": False,
               "finish_reason": None, "error": None}
        records.append(rec)
        t = threading.Thread(
            target=one_request, daemon=True,
            args=(args.host, args.port, req, args.t0,
                  max(1.0, deadline - time.monotonic()), rec))
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    out = []
    for rec, t in zip(records, threads):
        rec = dict(rec)
        if t.is_alive() and not rec["done"]:
            rec["error"] = rec["error"] or "timeout"
        out.append(rec)
    with open(args.out, "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
