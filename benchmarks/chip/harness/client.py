"""The load generator: one process, one thread, one event loop.

Sends generated requests to the proxy over HTTP with `stream: true`, reads
the server-sent events as they arrive and writes the client's log
(`client_log.py` says what an entry holds). Open loop: each request goes
at its due time whatever the server does. Closed loop: each client sends
its next request when its last one has ended.
"""

from __future__ import annotations

import asyncio
import json
import time


def new_entry(request: dict, due: float) -> dict:
    return {"id": request["id"], "due": due, "sent": None, "headers": None,
            "status": None, "events": [], "finish_reason": None,
            "done": None, "error": None, "cut": None, "failed_at": None,
            "max_tokens": request["max_tokens"],
            "prompt_tokens": len(request["prompt_ids"]),
            "greedy": request["temperature"] == 0.0, "text": ""}


async def stream(session, url: str, body: dict, entry: dict) -> None:
    """One streamed completion; everything seen goes into `entry`."""
    entry["sent"] = time.time()
    try:
        async with session.post(url, json=body) as resp:
            entry["headers"] = time.time()
            entry["status"] = resp.status
            if resp.status != 200:
                entry["error"] = (await resp.text())[:300]
                return
            async for raw in resp.content:
                line = raw.strip()
                if not line.startswith(b"data:"):
                    continue
                payload = line[5:].strip()
                now = time.time()
                if payload == b"[DONE]":
                    if entry["finish_reason"] and not entry["error"]:
                        entry["done"] = now
                    break
                event = json.loads(payload)
                if "error" in event:
                    entry["error"] = str(event["error"])[:300]
                    continue
                choice = event["choices"][0]
                text = choice.get("text") or ""
                if text:
                    entry["events"].append([now, len(text)])
                    entry["text"] += text
                if choice.get("finish_reason"):
                    entry["finish_reason"] = choice["finish_reason"]
    except asyncio.CancelledError:
        entry["cut"] = time.time()
        raise
    except Exception as e:  # noqa: BLE001 - a failed request, counted
        entry["error"] = repr(e)[:300]
    finally:
        if entry["done"] is None and entry["cut"] is None:
            entry["failed_at"] = time.time()


class Load:
    """Runs a generated load against `url`; `body_of` turns a generated
    request into the HTTP body (the family knows the API's shape)."""

    def __init__(self, url: str, body_of):
        self.url, self.body_of = url, body_of
        self.log: list = []

    async def _session(self):
        import aiohttp

        return aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None))

    async def one_by_one(self, requests: list) -> list:
        """Warm-up: each request alone, to its end; not in the log."""
        entries = []
        async with await self._session() as session:
            for r in requests:
                entry = new_entry(r, time.time())
                await stream(session, self.url, self.body_of(r), entry)
                entries.append(entry)
        return entries

    async def open_loop(self, requests: list, t_open: float,
                        wait_for, drain_until: float) -> None:
        """Sends each request at `t_open + due_s`; then waits until the
        requests `wait_for` selects have ended or `drain_until` has come,
        and cuts what is still open."""
        async with await self._session() as session:
            tasks = {}
            for r in sorted(requests, key=lambda r: r["due_s"]):
                due = t_open + r["due_s"]
                delay = due - time.time()
                if delay > 0:
                    await asyncio.sleep(delay)
                entry = new_entry(r, due)
                self.log.append(entry)
                tasks[r["id"]] = asyncio.ensure_future(
                    stream(session, self.url, self.body_of(r), entry))
            awaited = [tasks[e["id"]] for e in self.log if wait_for(e)]
            if awaited:
                await asyncio.wait(
                    awaited, timeout=max(drain_until - time.time(), 0))
            await self._cut(tasks.values())

    async def closed_loop(self, requests: list, clients: int,
                          t_close: float) -> None:
        """`clients` loops, each through its own requests in order, until
        `t_close`; then cuts what is still open."""
        async with await self._session() as session:
            async def client(k: int):
                for r in (r for r in requests if r["client"] == k):
                    entry = new_entry(r, time.time())
                    self.log.append(entry)
                    await stream(session, self.url, self.body_of(r), entry)

            tasks = [asyncio.ensure_future(client(k))
                     for k in range(clients)]
            await asyncio.wait(tasks, timeout=max(t_close - time.time(), 0))
            await self._cut(tasks)

    @staticmethod
    async def _cut(tasks) -> None:
        tasks = list(tasks)
        for t in tasks:
            if not t.done():
                t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
