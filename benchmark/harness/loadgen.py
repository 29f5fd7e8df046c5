"""Load generators for the served cells: jax-free worker processes that
send Envoy RLS v3 requests over gRPC, so the load plane stays off the
server's interpreter lock and never touches the chip.

Open loop: each worker sends its share of a seeded schedule at the due
times, whatever the server does, and each request is timed from when it
was due. Closed loop: each worker keeps `concurrency` requests
outstanding on as many threads. Every request waits up to `wait_s` for
its answer (a minute, far past Envoy's deadline): an answer that comes
late is late, not failed. Either way a worker returns, per request:
its index, due / sent / done times (perf_counter, one clock across the
host's processes), sent / done wall times (the clock the server's windows
follow), a status, and each descriptor's code."""

from __future__ import annotations

import functools
import threading
import time

import numpy as np

from .wire import RLS_V3_METHOD, decode_status_codes, encode_request

ST_NONE, ST_OK, ST_NO_ANSWER, ST_ERROR = 0, 1, 2, 3


class _Log:
    """Preallocated per-request records of one phase."""

    def __init__(self, n: int, n_desc: int):
        self.idx = np.full(n, -1, dtype=np.int64)
        self.t_due = np.full(n, np.nan)
        self.t_sent = np.full(n, np.nan)
        self.t_done = np.full(n, np.nan)
        self.wall_sent = np.full(n, np.nan)
        self.wall_done = np.full(n, np.nan)
        self.status = np.zeros(n, dtype=np.int8)
        self.resp = [None] * n
        self.n_desc = n_desc

    def finish(self, used: int) -> dict:
        codes = np.zeros((used, self.n_desc), dtype=np.int8)
        malformed = np.zeros(used, dtype=bool)
        for j in range(used):
            if self.status[j] == ST_OK:
                got = decode_status_codes(self.resp[j])
                if len(got) == self.n_desc:
                    codes[j] = got
                else:
                    malformed[j] = True
        return {
            "idx": self.idx[:used], "t_due": self.t_due[:used],
            "t_sent": self.t_sent[:used], "t_done": self.t_done[:used],
            "wall_sent": self.wall_sent[:used], "wall_done": self.wall_done[:used],
            "status": self.status[:used], "codes": codes, "malformed": malformed,
        }


def _record(log: _Log, j: int, grpc, fut) -> None:
    log.t_done[j] = time.perf_counter()
    log.wall_done[j] = time.time()
    try:
        log.resp[j] = fut.result()
        log.status[j] = ST_OK
    except grpc.RpcError as e:
        log.status[j] = ST_NO_ANSWER if e.code() == grpc.StatusCode.DEADLINE_EXCEEDED else ST_ERROR


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.perf_counter()
        if d <= 0:
            return
        if d > 3e-4:
            time.sleep(d - 2e-4)


def run_open(call, grpc, payloads, idx, offsets, t0: float, wait_s: float, n_desc: int) -> dict:
    n = len(idx)
    log = _Log(n, n_desc)
    left = [n]
    all_done = threading.Event()
    lock = threading.Lock()

    def cb(j, fut):
        _record(log, j, grpc, fut)
        with lock:
            left[0] -= 1
            if left[0] == 0:
                all_done.set()

    for j in range(n):
        due = t0 + offsets[j]
        _sleep_until(due)
        log.idx[j] = idx[j]
        log.t_due[j] = due
        log.t_sent[j] = time.perf_counter()
        log.wall_sent[j] = time.time()
        fut = call.future(payloads[idx[j]], timeout=wait_s)
        fut.add_done_callback(functools.partial(cb, j))
    if n:
        all_done.wait(wait_s + 30.0)
    return log.finish(n)


def run_closed(call, grpc, payloads, idx, t0: float, t_end: float, concurrency: int,
               wait_s: float, n_desc: int) -> dict:
    """`concurrency` threads, each sending its next request when the last
    returns, from its own stride of the payload pool, until t_end."""
    n_pool = len(idx)
    logs = [_Log(n_pool // concurrency + 1, n_desc) for _ in range(concurrency)]
    used = [0] * concurrency

    def client(c: int) -> None:
        log = logs[c]
        _sleep_until(t0)
        j = 0
        for p in range(c, n_pool, concurrency):
            if time.perf_counter() >= t_end:
                break
            log.idx[j] = idx[p]
            log.t_due[j] = log.t_sent[j] = time.perf_counter()
            log.wall_sent[j] = time.time()
            try:
                log.resp[j] = call(payloads[idx[p]], timeout=wait_s)
                log.status[j] = ST_OK
            except grpc.RpcError as e:
                log.status[j] = (ST_NO_ANSWER if e.code() == grpc.StatusCode.DEADLINE_EXCEEDED
                                 else ST_ERROR)
            log.t_done[j] = time.perf_counter()
            log.wall_done[j] = time.time()
            j += 1
        used[c] = j

    ths = [threading.Thread(target=client, args=(c,)) for c in range(concurrency)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    parts = [logs[c].finish(used[c]) for c in range(concurrency)]
    return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}


def worker_main(target: str, domain: str, jobs, results) -> None:
    """A worker process: builds its payloads, then runs phases until None."""
    import grpc

    channel = grpc.insecure_channel(target)
    call = channel.unary_unary(RLS_V3_METHOD)
    payloads: list = []
    try:
        while True:
            job = jobs.get()
            if job is None:
                return
            kind = job["kind"]
            if kind == "payloads":
                payloads = [encode_request(domain, descs) for descs in job["descriptors"]]
                grpc.channel_ready_future(channel).result(timeout=60)
                results.put({"kind": "ready"})
            elif kind == "open":
                results.put(run_open(call, grpc, payloads, job["idx"], job["offsets"],
                                     job["t0"], job["wait_s"], job["n_desc"]))
            elif kind == "closed":
                results.put(run_closed(call, grpc, payloads, job["idx"], job["t0"],
                                       job["t_end"], job["concurrency"], job["wait_s"],
                                       job["n_desc"]))
    finally:
        channel.close()


class Workers:
    """W spawned worker processes; stopped (and waited for) by close()."""

    def __init__(self, n: int):
        import multiprocessing as mp

        self.ctx = mp.get_context("spawn")
        self.n = n
        self.jobs = [self.ctx.Queue() for _ in range(n)]
        self.results = [self.ctx.Queue() for _ in range(n)]
        self.procs = []

    def start(self, target: str, domain: str) -> None:
        for w in range(self.n):
            p = self.ctx.Process(target=worker_main,
                                 args=(target, domain, self.jobs[w], self.results[w]),
                                 name=f"bench-loadgen-{w}", daemon=True)
            p.start()
            self.procs.append(p)

    def run(self, jobs: list, timeout: float) -> list:
        """Send one job per worker; wait for each one's reply."""
        for w, job in enumerate(jobs):
            self.jobs[w].put(job)
        return [self.results[w].get(timeout=timeout) for w in range(len(jobs))]

    def close(self) -> None:
        for q in self.jobs:
            q.put(None)
        for p in self.procs:
            p.join(timeout=30)
            if p.is_alive():
                p.kill()
                p.join(timeout=10)
