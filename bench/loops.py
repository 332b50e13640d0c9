"""The two load generators a traffic file can name.

``closed_batch``: one client sends back-to-back ``search`` calls, each a
block of ``q`` fresh queries, each waiting for the previous answer.

``open``: arrivals on a schedule fixed by the seed (see
``gen.open_schedule``).  A read is one query sent through ``submit``; an
update replaces an existing row through ``ingest`` on one writer thread, in
arrival order, and once acknowledged its embedding is sent as a probe, which
must come back as its own top-1.  Latency runs from each request's due time.

Queries, and an update's new embedding, are drawn by ``gen.queries`` under
the configuration's query law.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time

import numpy as np

from bench import gen

DRAIN_S = 60.0   # how long past the window an answer is waited for


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Request:
    x: np.ndarray
    kind: str                    # read | probe
    due: float                   # perf_counter seconds
    sent: float = 0.0
    done: float = 0.0
    vals: np.ndarray = None
    rows: np.ndarray = None
    error: str = ""
    update: int = -1             # probe: index of its update in the log


@dataclasses.dataclass
class Ack:
    gid: int
    x: np.ndarray
    due: float
    acked: float
    ingest_s: float


class ClosedBatch:
    def __init__(self, svc, index, cfg: dict, traffic: dict, seed: int):
        self.svc, self.index = svc, index
        self.q = traffic["q"]
        self.rng = gen.rng_for(seed, "queries")
        self.cfg = cfg
        self.requests: list[Request] = []
        self.acks: list[Ack] = []
        self.warm_acks: list[Ack] = []

    def warm(self) -> None:
        x = gen.queries(self.cfg, gen.rng_for(0, "warm"), self.q)
        self.svc.search(x)

    def passes(self) -> list:
        return [self.q] * (len(self.requests) // self.q)

    def run(self, seconds: float) -> dict:
        start = time.perf_counter()
        end = start + seconds
        last = start
        while last < end:
            xs = gen.queries(self.cfg, self.rng, self.q)
            with _annotate("bench.search"):
                sent = time.perf_counter()
                vals, rows = self.svc.search(xs)
                last = time.perf_counter()
            for i in range(self.q):
                self.requests.append(Request(xs[i], "read", sent, sent, last, vals[i], rows[i]))
        n = len(self.requests)
        return {"start": start, "qps": n / (last - start), "reads": n, "lateness_s": 0.0}


class OpenLoop:
    def __init__(self, svc, index, cfg: dict, traffic: dict, seed: int):
        self.svc, self.index, self.cfg, self.traffic = svc, index, cfg, traffic
        self.seed = seed
        self.requests: list[Request] = []
        self.acks: list[Ack] = []
        self.warm_acks: list[Ack] = []
        self.lock = threading.Lock()

    # -- set-up --------------------------------------------------------------
    def warm(self) -> None:
        """Warm every Q bucket the frontend can flush, after one update if the
        mix has updates (the first update changes the snapshot's shapes)."""
        rng = gen.rng_for(self.seed, "warm")
        if self.traffic.get("update_share", 0.0) > 0:
            gid = int(gen.zipf_ids(rng, 1, self.cfg["n_rows"], self.traffic["update_key_theta"])[0])
            x = gen.queries(self.cfg, rng, 1)[0]
            t = time.perf_counter()
            self.svc.ingest(x[None, :], ids=[gid])
            now = time.perf_counter()
            self.warm_acks.append(Ack(gid, x, t, now, now - t))
        xs = gen.queries(self.cfg, rng, self.cfg["frontend"]["max_batch"])
        q = 1
        while q <= xs.shape[0]:
            self.index.query_batch(xs[:q])
            q *= 2

    def passes(self) -> list:
        return self._passes

    # -- the window ----------------------------------------------------------
    def _send(self, req: Request) -> None:
        req.sent = time.perf_counter()
        fut = self.svc.submit(req.x)

        def finish(f, req=req):
            req.done = time.perf_counter()
            try:
                req.vals, req.rows = f.result()
            except Exception as e:   # the answer is recorded as failed, not raised here
                req.error = f"{type(e).__name__}: {e}"

        fut.add_done_callback(finish)
        with self.lock:
            self.requests.append(req)

    def _writer(self, todo: "queue.Queue", stop: threading.Event) -> None:
        while not stop.is_set():
            item = todo.get()
            if item is None:
                return
            gid, x, due = item
            with _annotate("bench.ingest"):
                t = time.perf_counter()
                self.svc.ingest(x[None, :], ids=[gid])
                now = time.perf_counter()
            self.acks.append(Ack(gid, x, due, now, now - t))
            self._send(Request(x, "probe", due, update=len(self.warm_acks) + len(self.acks) - 1))

    def run(self, seconds: float, rate: float | None = None) -> dict:
        traffic = dict(self.traffic, rate_per_s=rate or self.traffic["rate_per_s"])
        rng = gen.rng_for(self.seed, f"schedule{rate or ''}")
        sched = gen.open_schedule(traffic, self.cfg["n_rows"], seconds, rng)
        xs = gen.queries(self.cfg, rng, sched.due.shape[0])
        hist0 = dict(self.svc.dispatch_info()["frontend"]["batch_histogram"])
        todo: queue.Queue = queue.Queue()
        stop = threading.Event()
        writer = threading.Thread(target=self._writer, args=(todo, stop), daemon=True)
        writer.start()
        n_before = len(self.requests)
        start = time.perf_counter()
        late, upd = [], 0
        try:
            for i, due_rel in enumerate(sched.due):
                due = start + due_rel
                wait = due - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                late.append(time.perf_counter() - due)
                if sched.is_update[i]:
                    todo.put((int(sched.update_ids[upd]), xs[i], due))
                    upd += 1
                else:
                    self._send(Request(xs[i], "read", due))
        finally:
            todo.put(None)
            writer.join(timeout=seconds + DRAIN_S)
            stop.set()   # updates still queued past the drain are never sent
        deadline = start + seconds + DRAIN_S
        with self.lock:
            mine = self.requests[n_before:]
        for req in mine:
            while req.done == 0.0 and time.perf_counter() < deadline:
                time.sleep(0.005)
        hist1 = self.svc.dispatch_info()["frontend"]["batch_histogram"]
        self._passes = [int(b) for b, c in hist1.items() for _ in range(c - hist0.get(b, 0))]
        reads = [r for r in mine if r.kind == "read" and r.done and not r.error]
        last = max((r.done for r in reads), default=start)
        return {
            "start": start,
            "qps": len(reads) / (last - start) if reads else 0.0,
            "reads": len(reads),
            "lateness_s": float(np.max(late)) if late else 0.0,
            "writer_alive": writer.is_alive(),
        }

    def read_latencies_ms(self) -> np.ndarray:
        return np.array([(r.done - r.due) * 1e3 for r in self.requests
                         if r.kind == "read" and r.done and not r.error])

    def visibility(self) -> tuple:
        """(latencies in ms of updates shown by their probe, probes that missed).

        A probe whose answer lacks its row is excused only when a later
        update of the same id was acknowledged before that answer came.
        """
        all_acks = self.warm_acks + self.acks
        seen, missed = [], 0
        for req in self.requests:
            if req.kind != "probe" or not req.done or req.error:
                continue
            ack = all_acks[req.update]
            if req.rows is not None and int(req.rows[0]) == ack.gid:
                seen.append((req.done - ack.due) * 1e3)
            elif not any(a.gid == ack.gid and ack.acked < a.acked <= req.done
                         for a in all_acks[req.update + 1:]):
                missed += 1
        return np.array(seen), missed


LOOPS = {"closed_batch": ClosedBatch, "open": OpenLoop}
