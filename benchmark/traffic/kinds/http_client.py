"""The load generator's process. It never imports jax or the program:
it reads a schedule (due time, prompt, max_new_tokens per request), sends
each request at its due time whether or not earlier ones have answered
(open loop), reads the streamed answer line by line, and stamps every
token with the clock that parent and child share (CLOCK_MONOTONIC).

usage: http_client.py <schedule.json> <results.json>
schedule.json: {"url", "window_s", "drain_s", "request_timeout_s",
"requests": [{"due_s", "prompt", "max_new_tokens", "temperature"}]}.
When the schedule is loaded it prints `ready` and reads the window's
first instant (monotonic seconds) from stdin: the window opens when the
generator can send, however long this process took to start."""
from __future__ import annotations

import http.client
import json
import sys
import threading
import time
from urllib.parse import urlparse


def one(host, port, t0, i, req, out, timeout):
    rec = {"i": i, "due_s": req["due_s"], "status": None, "error": None,
           "token_s": [], "tokens": [], "done": False}
    try:
        body = json.dumps({
            "prompt": req["prompt"],
            "max_new_tokens": req["max_new_tokens"],
            "temperature": req["temperature"], "stream": True}).encode()
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        rec["sent_s"] = time.monotonic() - t0
        conn.request("POST", "/generate", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read().decode(errors="replace")[:200]
        else:
            while True:
                line = resp.readline()
                if not line:
                    break
                now = time.monotonic() - t0
                msg = json.loads(line)
                if "token" in msg:
                    rec["tokens"].append(int(msg["token"]))
                    rec["token_s"].append(now)
                elif msg.get("done"):
                    rec["done"] = True
                    rec["end_s"] = now
                    rec["final_tokens"] = msg.get("tokens")
                elif "error" in msg:
                    rec["error"] = str(msg["error"])[:200]
        conn.close()
    except Exception as e:  # noqa: BLE001 - a failed request is a record
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    out[i] = rec


def main(argv):
    with open(argv[1]) as f:
        plan = json.load(f)
    u = urlparse(plan["url"])
    print("ready", flush=True)
    t0 = float(sys.stdin.readline())
    reqs = plan["requests"]
    out = [None] * len(reqs)
    threads = []
    for i, req in enumerate(reqs):
        delay = t0 + req["due_s"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        th = threading.Thread(
            target=one, args=(u.hostname, u.port, t0, i, req, out,
                              plan["request_timeout_s"]), daemon=True)
        th.start()
        threads.append(th)
    deadline = t0 + plan["window_s"] + plan["drain_s"]
    for th in threads:
        th.join(max(deadline - time.monotonic(), 0.0))
    for i, rec in enumerate(out):
        if rec is None:
            out[i] = {"i": i, "due_s": reqs[i]["due_s"], "status": None,
                      "error": "unfinished at the drain limit",
                      "token_s": [], "tokens": [], "done": False}
    with open(argv[2], "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
