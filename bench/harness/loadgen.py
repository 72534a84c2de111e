"""Load generator: drives the served HTTP endpoint from its own process.

    python loadgen.py SPEC.json OUT.json

It never imports JAX, so it holds no chip and shares no GIL with the
server.  One thread runs an asyncio loop; each request opens its own
connection.  SPEC holds the resolved mix, the seed, the window length,
the endpoint and the vocabulary.  On standard output it prints
``START <monotonic seconds>`` when the window opens and ``END`` when the
last request is answered; OUT receives one record per request:

  due     monotonic time the request was due (open loop) or sent (closed)
  sent    when it was written to the socket
  status  HTTP status, or 0 when the connection failed
  first, times   arrival of the first / every streamed token
  done    arrival of the last byte
  tokens  served tokens (generate), classes (infer: per member)

Every latency is taken from ``due``.  A refused (429/504) or failed
request counts as a miss in every tail (see ``metrics``).
"""

from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import traffic  # noqa: E402  (sibling module, also JAX-free)

# requests still open this long after the window closes are cut and
# counted as failed
DRAIN_S = 120.0


async def _http(host, port, path, body: bytes, on_event=None):
    """POST ``body``; returns (status, parsed JSON or None).  Chunked
    NDJSON streams hand each event to ``on_event`` as it arrives."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        writer.write(b"POST %s HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\nConnection: close\r\n\r\n"
                     % (path.encode(), len(body)) + body)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        headers = {}
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode("latin-1").partition(":")
            headers[k.strip().lower()] = v.strip()
        if headers.get("transfer-encoding") == "chunked":
            last = None
            while True:
                size = int((await reader.readline()).strip() or b"0", 16)
                if size == 0:
                    break
                data = await reader.readexactly(size + 2)
                ev = json.loads(data[:-2])
                if on_event is not None:
                    on_event(ev)
                last = ev
            return status, last
        n = int(headers.get("content-length", 0))
        data = await reader.readexactly(n) if n else b""
        return status, (json.loads(data) if data else None)
    finally:
        writer.close()


async def _one(spec, mix, req, rec, due):
    now = time.monotonic
    if due > now():
        await asyncio.sleep(due - now())
    rec["due"] = due
    rec["sent"] = now()
    body = json.dumps(traffic.payload(mix, req, spec["seed"],
                                      spec["vocab"])).encode()
    times = []
    out = {}

    def on_event(ev):
        if ev.get("event") == "token":
            times.append(now())
        elif ev.get("event") == "done":
            out["tokens"] = ev.get("tokens")
            out["finish_reason"] = ev.get("finish_reason")
        elif ev.get("event") == "error":
            out["error"] = ev.get("error")

    path = "/v1/generate" if mix["plane"] == "generate" else "/v1/infer"
    try:
        status, last = await _http(spec["host"], spec["port"], path, body,
                                   on_event)
    except (OSError, asyncio.IncompleteReadError, ValueError) as e:
        status, last = 0, {"error": repr(e)}
    rec["done"] = now()
    rec["status"] = status
    if mix["plane"] == "generate":
        rec["times"] = times
        rec["first"] = times[0] if times else None
        rec["tokens"] = out.get("tokens")
        rec["finish_reason"] = out.get("finish_reason")
        if status == 200 and ("error" in out or out.get("tokens") is None):
            rec["status"] = 0
    elif status == 200 and isinstance(last, dict):
        rec["classes"] = {k: v for k, v in last.items()
                          if k.startswith("model_")}
    if status != 200 and isinstance(last, dict):
        rec["error"] = str(last.get("error"))[:200]


async def _run(spec, out_path):
    mix = spec["mix"]
    reqs = traffic.schedule(mix, spec["seed"], spec["seconds"])
    records = [dict(i=r["i"]) for r in reqs]
    t0 = time.monotonic() + 0.2
    print(f"START {t0!r}", flush=True)
    end = t0 + spec["seconds"]
    if mix["loop"] == "open":
        tasks = [asyncio.ensure_future(_one(spec, mix, r, rec,
                                            t0 + r["due_s"]))
                 for r, rec in zip(reqs, records)]
    else:
        nxt = iter(range(len(reqs)))
        used = []

        async def client():
            await asyncio.sleep(max(0.0, t0 - time.monotonic()))
            while time.monotonic() < end:
                k = next(nxt)
                used.append(k)
                await _one(spec, mix, reqs[k], records[k], time.monotonic())

        tasks = [asyncio.ensure_future(client())
                 for _ in range(traffic.concurrency(mix,
                                                    spec["num_slots"]))]
    done, pending = await asyncio.wait(tasks, timeout=end + DRAIN_S
                                       - time.monotonic())
    for t in pending:
        t.cancel()
    if mix["loop"] == "closed":
        records = [records[k] for k in sorted(used)]
    for rec in records:
        if "status" not in rec and "due" in rec:
            rec["status"] = 0
            rec["error"] = "not answered before the drain limit"
    Path(out_path).write_text(json.dumps({"t0": t0, "end": end,
                                          "records": records}))
    print("END", flush=True)


def main(argv) -> int:
    spec = json.loads(Path(argv[1]).read_text())
    asyncio.run(_run(spec, argv[2]))
    assert "jax" not in sys.modules, "the load generator imported JAX"
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
