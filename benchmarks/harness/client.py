"""The load generator's client: one asyncio loop, raw HTTP/1.1 + SSE (copied
in idea from infinistore_tpu/loadgen.py, which lives in the program and may
change).  Every request is timed from when it was DUE, not from when it was
sent, and the pacer reports how late it ran.  Never imports JAX."""

from __future__ import annotations

import asyncio
import json
import time
from typing import List, Optional

TIMEOUT_S = 300.0
clock = time.perf_counter


async def post(port: int, body: dict, t_due: Optional[float] = None,
               path: str = "/v1/completions") -> dict:
    """One completion request.  Returns a row: t_due, t_send, t_first,
    t_last, t_done, tokens, events [(t, n_tokens)], status, ok, error, and
    for a non-streamed request the parsed ``payload``.  ``ok`` is exact:
    status 200, no error event, and exactly ``max_tokens`` tokens."""
    t_send = clock()
    row = {"t_due": t_send if t_due is None else t_due, "t_send": t_send,
           "t_first": None, "t_last": None, "t_done": None, "tokens": 0,
           "events": [], "status": 0, "ok": False, "error": None,
           "asked": body.get("max_tokens"), "prompt_tokens": len(body["prompt"])}
    writer = None
    try:
        payload = json.dumps(body).encode()
        reader, writer = await asyncio.wait_for(
            asyncio.open_connection("127.0.0.1", port, limit=1 << 22), TIMEOUT_S)
        writer.write((f"POST {path} HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
                      f"Content-Type: application/json\r\n"
                      f"Content-Length: {len(payload)}\r\n"
                      f"Connection: close\r\n\r\n").encode() + payload)
        await asyncio.wait_for(writer.drain(), TIMEOUT_S)
        status_line = await asyncio.wait_for(reader.readline(), TIMEOUT_S)
        row["status"] = int(status_line.split(None, 2)[1])
        headers = {}
        while True:
            line = await asyncio.wait_for(reader.readline(), TIMEOUT_S)
            if line in (b"\r\n", b"\n", b""):
                break
            k, _, v = line.decode(errors="replace").partition(":")
            headers[k.strip().lower()] = v.strip()
        if row["status"] != 200 or not body.get("stream"):
            n = headers.get("content-length")
            raw = await asyncio.wait_for(
                reader.readexactly(int(n)) if n is not None else reader.read(),
                TIMEOUT_S)
            if row["status"] != 200:
                row["error"] = raw.decode(errors="replace")[:300]
            else:
                row["payload"] = json.loads(raw)
                ids = row["payload"]["choices"][0].get("token_ids") or ()
                row["tokens"] = len(ids)
                row["t_first"] = row["t_last"] = clock()
                row["events"].append((row["t_first"], len(ids)))
        else:
            done = False
            while not done:
                raw = await asyncio.wait_for(reader.readline(), TIMEOUT_S)
                if not raw:
                    row["error"] = "stream ended without [DONE]"
                    break
                line = raw.strip()
                if not line.startswith(b"data: "):
                    continue
                data = line[6:]
                if data == b"[DONE]":
                    done = True
                    break
                ev = json.loads(data)
                if "error" in ev:
                    row["error"] = str(ev["error"])[:300]
                    break
                n_new = len(ev["choices"][0].get("token_ids") or ())
                if n_new:
                    now = clock()
                    if row["t_first"] is None:
                        row["t_first"] = now
                    row["t_last"] = now
                    row["tokens"] += n_new
                    row["events"].append((now, n_new))
    except asyncio.CancelledError:
        # the closed loop's window ended: what arrived so far still counts as
        # work of the window, the request itself is neither done nor failed
        row["cancelled"] = True
    except Exception as e:  # noqa: BLE001 -- a failed request is a counted row
        row["error"] = repr(e)[:300]
    finally:
        if writer is not None:
            writer.close()
    row["t_done"] = clock()
    row["ok"] = (row["status"] == 200 and row["error"] is None
                 and row["tokens"] == row["asked"])
    return row


async def gather_posts(port: int, bodies: List[dict], concurrency: int) -> List[dict]:
    """Send ``bodies`` with at most ``concurrency`` in flight, in order."""
    sem = asyncio.Semaphore(concurrency)

    async def one(b):
        async with sem:
            return await post(port, b)

    return await asyncio.gather(*(one(b) for b in bodies))


async def open_loop(port: int, schedule: List[dict], t0: float) -> List[dict]:
    """Fire ``schedule`` ([{due, body}], due in seconds from t0) whether or
    not earlier requests finished.  Each row gets ``late_s``: how long after
    its due time the pacer sent it."""
    tasks = []
    for item in schedule:
        due = t0 + item["due"]
        delay = due - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(post(port, item["body"], t_due=due)))
    rows = list(await asyncio.gather(*tasks))
    for r, item in zip(rows, schedule):
        r["late_s"] = r["t_send"] - r["t_due"]
        r["kind"], r["due"] = item.get("kind"), item["due"]
    return rows


async def closed_loop(port: int, schedule, clients: int,
                      t_stop: float) -> List[dict]:
    """``clients`` callers, each sending the next request of ``schedule`` (an
    iterator without end) when its last one completed.  At ``t_stop`` the requests in flight are dropped
    (their connections closed): their rows are marked ``cancelled`` and keep
    the tokens that had arrived."""
    it = iter(schedule)
    rows: List[dict] = []

    async def caller():
        while clock() < t_stop:
            item = next(it)
            task = asyncio.ensure_future(post(port, item["body"]))
            _, pending = await asyncio.wait({task}, timeout=max(0.0, t_stop - clock()))
            if pending:
                task.cancel()
            r = await task
            r["late_s"], r["kind"], r["due"] = 0.0, item.get("kind"), None
            rows.append(r)

    await asyncio.gather(*(caller() for _ in range(clients)))
    return rows


async def get(port: int, path: str, timeout: float = 30.0) -> bytes:
    reader, writer = await asyncio.wait_for(
        asyncio.open_connection("127.0.0.1", port, limit=1 << 24), timeout)
    try:
        writer.write((f"GET {path} HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
                      f"Connection: close\r\n\r\n").encode())
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(), timeout)
    finally:
        writer.close()
    head, _, rest = raw.partition(b"\r\n\r\n")
    if b" 200 " not in head.split(b"\r\n", 1)[0]:
        raise OSError(f"GET {path}: {head[:80]!r}")
    if b"chunked" in head.lower():
        out, i = b"", 0
        while True:
            j = rest.index(b"\r\n", i)
            n = int(rest[i:j], 16)
            if n == 0:
                return out
            out += rest[j + 2:j + 2 + n]
            i = j + 2 + n + 2
    return rest
