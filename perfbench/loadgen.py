"""The open-loop load generator: a process of its own that never imports
JAX, so that it neither holds the chip nor shares the server's interpreter
lock. One thread, asyncio, plain sockets.

Protocol with the parent (``perfbench/jobs/serve.py``), over the pipes:

1. the parent writes one JSON line: ``{"url", "mix", "seed", "seconds",
   "vocab_size", "warmup"}``;
2. the child builds the requests (``perfbench/traffic.py``), sends
   ``warmup`` short requests one after another over HTTP, then writes
   ``READY``;
3. the parent writes ``GO``; the child notes the start on the monotonic
   clock (the same clock in every process of a Linux host), sends every
   request when it is DUE, whatever became of the earlier ones, and reads
   each answer's SSE events as they come;
4. ``drain_seconds`` after the window it gives up on what is unfinished and
   writes one JSON line: the start, and for every request its due and sent
   times and the arrival time of each token (seconds from the start), its
   tokens, the server's record, and whether it finished.
"""

import asyncio
import json
import sys
import time
from urllib.parse import urlparse

from perfbench import traffic


async def _generate(host: str, port: int, req: dict, out: dict, t0: float):
    """POST one request and read its SSE stream into ``out``."""
    body = json.dumps({"prompt": req["prompt"],
                       "max_new_tokens": req["max_new_tokens"],
                       "stream": True}).encode()
    head = (f"POST /v1/generate HTTP/1.1\r\nHost: {host}:{port}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        out["sent_s"] = time.monotonic() - t0
        writer.write(head.encode() + body)
        await writer.drain()
        status = (await reader.readline()).split()
        out["status"] = int(status[1]) if len(status) > 1 else 0
        while (await reader.readline()).strip():
            pass  # headers
        if out["status"] != 200:
            out["error"] = (await reader.read(4096)).decode(errors="replace")
            return
        event = ""
        while True:
            line = await reader.readline()
            if not line:
                out.setdefault("error", "stream closed before done")
                return
            line = line.decode().rstrip("\n")
            if line.startswith("event: "):
                event = line[7:]
            elif line.startswith("data: "):
                now = time.monotonic() - t0
                data = json.loads(line[6:])
                if event == "token":
                    out["tokens"].append(data["token"])
                    out["arrivals"].append(now)
                elif event == "done":
                    out["record"], out["ok"] = data, True
                    return
                else:
                    out["error"] = data.get("reason", event)
                    return
    except (OSError, ValueError, asyncio.IncompleteReadError) as e:
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


async def _one(host, port, req, out, t0):
    delay = t0 + req["due_s"] - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    await _generate(host, port, req, out, t0)


async def _window(host, port, reqs, outs, t0, deadline_s):
    tasks = [asyncio.ensure_future(_one(host, port, r, o, t0))
             for r, o in zip(reqs, outs)]
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, t0 + deadline_s - time.monotonic()))
    for t in pending:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


def _blank(req: dict) -> dict:
    return {"due_s": req["due_s"], "prompt_len": len(req["prompt"]),
            "max_new_tokens": req["max_new_tokens"], "tokens": [],
            "arrivals": [], "ok": False}


def main() -> int:
    cfg = json.loads(sys.stdin.readline())
    url = urlparse(cfg["url"])
    host, port = url.hostname, url.port
    reqs = traffic.requests(cfg["mix"], cfg["seed"], cfg["seconds"],
                            cfg["vocab_size"])
    for req in reqs[:int(cfg.get("warmup", 0))]:
        out = _blank(req)
        asyncio.run(_generate(host, port, {**req, "max_new_tokens": 2},
                              out, time.monotonic()))
        if not out["ok"]:
            print(json.dumps({"warmup_failed": out}), flush=True)
            return 1
    print("READY", flush=True)
    if sys.stdin.readline().strip() != "GO":
        return 1
    outs = [_blank(r) for r in reqs]
    t0 = time.monotonic()
    asyncio.run(_window(host, port, reqs, outs, t0,
                        cfg["seconds"] + cfg["mix"].get("drain_seconds", 20)))
    print(json.dumps({"t0_monotonic": t0, "requests": outs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
