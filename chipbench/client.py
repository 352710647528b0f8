"""Load client: sends a plan's requests (``plan.py``) to the server over
HTTP/SSE as the plan says, and records when each token arrived.  Standard
library only: it never imports JAX, so it neither holds the chip nor
shares the server's interpreter.

    python client.py PLAN.json RESULT.json PORT SECONDS GRACE

It prints ``t0 <perf_counter>`` when the window opens (the clock is
CLOCK_MONOTONIC, shared with the server process).  A request is sent when
it is due: ``delay`` seconds after the window opened, or after the
request it waits for finished; it is timed from then, so a late send
counts against the server.  None is sent at or after the close.  After
the close it waits up to GRACE seconds for the requests sent in the
window to finish, then writes RESULT.json: per request when it was due
and sent, each completion's token times and tokens and the server's
final event, and how late the sender ran.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time

from plan import prompt_ids

now = time.perf_counter


async def _post(port: int, body: bytes):
    reader, writer = await asyncio.open_connection("127.0.0.1", port,
                                                   limit=2 ** 22)
    writer.write(b"POST /v1/generate HTTP/1.0\r\nHost: 127.0.0.1\r\n"
                 b"Content-Type: application/json\r\n"
                 + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    await writer.drain()
    return reader, writer


async def request(port: int, body: bytes, rec: dict, gate) -> None:
    """Send one streaming request; fill ``rec`` with token times.  ``gate``
    admits a few connects at a time: the server's listen queue is short,
    and a burst of connects beyond it would wait out TCP retransmits."""
    writer = None
    try:
        async with gate:
            rec["sent"] = now()
            reader, writer = await _post(port, body)
        status = await reader.readline()
        if b" 200 " not in status:
            rec["error"] = status.decode().strip()
            return
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass
        event = None
        while True:
            line = await reader.readline()
            if not line:
                rec.setdefault("error", "stream closed before done")
                return
            line = line.strip()
            if line.startswith(b"event:"):
                event = line[6:].strip().decode()
            elif line.startswith(b"data:"):
                data = json.loads(line[5:])
                if event == "token":
                    c = rec["choices"][data["choice"]]
                    c["times"].append(now())
                    c["tokens"].append(data["token"])
                elif event == "done":
                    rec["done"] = now()
                    for c in data["choices"]:
                        mine = rec["choices"][c["index"]]
                        mine["output"] = c["tokens"]
                        mine["finish_reason"] = c["finish_reason"]
                    return
    except (OSError, asyncio.IncompleteReadError, ValueError, KeyError,
            IndexError) as e:
        rec["error"] = repr(e)
    finally:
        if writer is not None:
            writer.close()


def body_of(ids: list, req: dict) -> bytes:
    return (f'{{"prompt": {json.dumps(ids)}, "max_tokens": '
            f'{req["max_tokens"]}, "n": {req["n"]}, "stream": true}}'
            ).encode()


async def run(plan: dict, port: int, seconds: float, grace: float):
    reqs = plan["requests"]
    waiting = {}                            # id -> requests that wait for it
    for r in reqs:
        if r["after"] is not None:
            waiting.setdefault(r["after"], []).append(r)
    records, served, tasks = {}, {}, set()
    gate = asyncio.Semaphore(4)
    t0 = now()
    print(f"t0 {t0!r}", flush=True)
    t_end = t0 + seconds

    async def fire(req, due):
        delay = due - now()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = {"id": req["id"], "due": due, "sent": None, "done": None,
               "choices": [{"times": [], "tokens": []}
                           for _ in range(req["n"])]}
        records[req["id"]] = rec
        ids = prompt_ids(plan, req, served)
        await request(port, body_of(ids, req), rec, gate)
        if rec["done"] is None or "error" in rec:
            return
        served[req["id"]] = rec["choices"][0]["tokens"]
        for nxt in waiting.get(req["id"], ()):
            launch(nxt, now() + nxt["delay"])

    def launch(req, due):
        if due < t_end:
            task = asyncio.ensure_future(fire(req, due))
            tasks.add(task)
            task.add_done_callback(tasks.discard)

    for r in reqs:
        if r["after"] is None:
            launch(r, t0 + r["delay"])
    # until the close, requests keep being launched; after it ``launch``
    # refuses them, and those due in the window have the grace to finish
    while tasks and now() < t_end:
        await asyncio.wait(set(tasks), timeout=t_end - now())
    if tasks:
        await asyncio.wait(set(tasks), timeout=max(0.0, t_end + grace - now()))
    for t in list(tasks):
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    recs = [records[k] for k in sorted(records)]
    return {"t0": t0, "t_end": t_end, "records": recs}


def main(argv) -> None:
    plan_path, out_path, port, seconds, grace = argv
    with open(plan_path) as f:
        plan = json.load(f)
    result = asyncio.run(run(plan, int(port), float(seconds), float(grace)))
    late = sorted(r["sent"] - r["due"] for r in result["records"]
                  if r["sent"] is not None)
    if late:
        print(f"client: sender lateness p50 {late[len(late) // 2] * 1e3:.3f}"
              f" ms, max {late[-1] * 1e3:.3f} ms over {len(late)} sends",
              flush=True)
    result["lateness_s"] = late
    with open(out_path, "w") as f:
        json.dump(result, f)


if __name__ == "__main__":
    main(sys.argv[1:])
