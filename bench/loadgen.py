"""The load generator: a process of its own that never imports JAX.

Started by the serving driver before it touches the chip; speaks HTTP over
loopback to the gateway from ONE thread (asyncio), so that the clients do
not share the engine's interpreter and their own threads do not jitter the
clock.  Open loop: every request is sent when it is due, whether or not
earlier ones have finished, and every time is taken from when it was DUE.

Protocol, one JSON object a line:
  stdin   {"cmd": "warm", "url": ...}            -> {"warm": [...records]}
          {"cmd": "go", "url": ..., "t0": epoch} -> {"done": [...records]}
          {"cmd": "quit"}
The schedule itself is not sent: the child draws it from the same
parameters and seed with the same generator as the parent
(``traffic.open_loop_http.make_schedule``).

Copied from ``paddle_tpu/inference/frontend/loadgen.py``: the shape of the
request body and of the SSE events (``http_completion``).  That module's
closed loop is not used (a slow server would get less load).
"""
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench.traffic.open_loop_http import make_schedule, warm_requests  # noqa: E402


async def one_request(host, port, req, t0, cancel_at, record):
    """POST one streaming completion; fills ``record`` as events arrive.
    Times are seconds since ``t0``; ``due`` is when it should have left."""
    body = json.dumps({"prompt": req["prompt"], "max_tokens": req["max_tokens"],
                       "stream": True}).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: {host}:{port}\r\n"
            f"Content-Type: application/json\r\nContent-Length: {len(body)}\r\n"
            f"Connection: close\r\n\r\n").encode()
    record["sent"] = time.time() - t0
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(head + body)
        await writer.drain()
        status_line = await reader.readline()
        record["http"] = int(status_line.split()[1])
        while True:
            timeout = None if cancel_at is None else max(
                0.0, cancel_at - (time.time() - t0))
            line = await asyncio.wait_for(reader.readline(), timeout)
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            payload = line[6:].strip()
            if payload == b"[DONE]":
                break
            evt = json.loads(payload)
            if "token" in evt:
                record["tokens"].append(evt["token"])
                record["times"].append(time.time() - t0)
            else:
                record["status"] = evt.get("status")
                record["replica"] = evt.get("replica")
    except asyncio.TimeoutError:
        record["status"] = record["status"] or "cancelled_at_close"
    except (OSError, ValueError, IndexError) as e:
        record["status"] = f"client_error: {type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


async def drive(url, requests, t0, cancel_at):
    host, port = url.split("//")[1].split(":")
    port = int(port)
    records, tasks = [], []
    for req in requests:
        delay = t0 + req["due"] - time.time()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = {"i": req["i"], "due": req["due"], "sent": None, "http": None,
               "status": None, "replica": None, "tokens": [], "times": []}
        records.append(rec)
        tasks.append(asyncio.ensure_future(
            one_request(host, port, req, t0, cancel_at, rec)))
    if tasks:
        await asyncio.wait(tasks)
    return records


def main():
    params = json.loads(sys.argv[1])
    seed, seconds = int(sys.argv[2]), float(sys.argv[3])
    schedule = make_schedule(params, seed, seconds)
    print(json.dumps({"ready": len(schedule)}), flush=True)
    for line in sys.stdin:
        msg = json.loads(line)
        if msg["cmd"] == "quit":
            break
        if msg["cmd"] == "warm":
            reqs = warm_requests(params, seed)
            recs = asyncio.run(drive(msg["url"], reqs, time.time(), None))
            print(json.dumps({"warm": recs}), flush=True)
        elif msg["cmd"] == "go":
            # unfinished requests are waited for until drain_s past the close
            cancel_at = seconds + float(params.get("drain_s", 60.0))
            recs = asyncio.run(drive(msg["url"], schedule, msg["t0"],
                                     cancel_at))
            print(json.dumps({"done": recs}), flush=True)


if __name__ == "__main__":
    main()
