"""The load generator: a process of its own, so that client threads do not
share the serving loop's interpreter lock, talking to the server's
``Frontend`` through ``ServeClient`` as a user's client would.

It never initialises a JAX backend (the server process holds the chip; the
parent also starts it with ``JAX_PLATFORMS=cpu``).  Two threads: this one
sends, ``ServeClient``'s reader stamps each frame as it arrives.

    parent -> stdin : "go <t0>"    window start on chipbench.clock
    stdout          : "ready", then one JSON line: the log of every request

Open loop: request i is sent at t0 + arrivals[i] whether or not earlier ones
finished, is timed from the instant it was due, and is followed until
``drain_s`` past the window; what still streams then is cancelled and logged
as such, with the tokens it got.  Closed loop: ``clients_per_slot`` x slots
clients, each sending its next request when its last returns; what is still
in flight when the window closes is cancelled.
"""

from __future__ import annotations

import argparse
import collections
import json
import queue
import sys
import time

from tpu_dist.serve.client import ServeClient

from . import traffic
from .clock import now


class _TimedClient(ServeClient):
    """Stamps token and terminal frames with the arrival time, in the reader
    thread, before the handle sees them."""

    def __init__(self, host, port):
        self.recv = collections.defaultdict(list)   # rid -> token instants
        self.ended = {}                             # rid -> (instant, status)
        self.finished = queue.SimpleQueue()
        super().__init__(host, port, connect_retry=60.0)

    def _dispatch(self, frame):
        kind, rid = frame.get("type"), frame.get("id")
        if kind == "token":
            self.recv[rid].append(now())
        elif kind in ("done", "error"):
            self.ended[rid] = (now(), "ok" if kind == "done" else
                               "error:" + str(frame.get("error")))
            self.finished.put(rid)
        super()._dispatch(frame)


def _sleep_until(t: float) -> None:
    time.sleep(max(0.0, t - now()))


class _Log:
    """Every request sent, and what became of it."""

    def __init__(self, client, mix, seed, vocab):
        self.client, self.mix, self.seed, self.vocab = client, mix, seed, vocab
        self.rows = {}    # rid -> (index, due, sent, prompt length, n_out, handle)

    def draw(self, index):
        prompt, n_out = traffic.request(self.mix, self.seed, index, self.vocab)
        return prompt.tolist(), n_out

    def send(self, index, prompt, n_out, due=None):
        """``due`` None: a closed-loop request, due the moment it is sent."""
        sent = now()
        h = self.client.submit(prompt, max_new_tokens=n_out)
        self.rows[h.id] = (index, sent if due is None else due, sent,
                           len(prompt), n_out, h)

    def unfinished(self):
        return [rid for rid in self.rows if rid not in self.client.ended]

    def wait_all(self, deadline):
        while self.unfinished() and now() < deadline:
            time.sleep(0.01)

    def cancel_rest(self) -> list:
        """Cancel what is still in flight; the log of every request."""
        live = set(self.unfinished())
        for rid in live:
            self.rows[rid][-1].cancel()
        self.wait_all(now() + 10.0)
        out = []
        for rid, (index, due, sent, n_prompt, n_out, h) in sorted(
                self.rows.items()):
            end, status = self.client.ended.get(rid, (None, "unfinished"))
            out.append({"i": index, "due": due, "sent": sent, "end": end,
                        "status": "cancelled" if rid in live else status,
                        "n_prompt": n_prompt, "n_out": n_out,
                        "recv": self.client.recv.get(rid, []),
                        "tokens": h.tokens()})
        return out


def _open_loop(log, seconds, wait_go):
    offsets = traffic.arrivals(log.mix, log.seed, seconds)
    drawn = [log.draw(i) for i in range(len(offsets))]   # before the window
    t0 = wait_go()
    due = t0 + offsets
    for i, t in enumerate(due):
        _sleep_until(t)
        log.send(i, *drawn[i], due=float(t))
    log.wait_all(t0 + seconds + float(log.mix["drain_s"]))
    return log.cancel_rest()


def _closed_loop(log, seconds, slots, wait_go):
    clients = int(log.mix["clients_per_slot"]) * slots
    drawn = [log.draw(i) for i in range(clients)]
    t0 = wait_go()
    _sleep_until(t0)
    for i in range(clients):
        log.send(i, *drawn[i])
    nxt, t_end = clients, t0 + seconds
    while True:
        try:    # a client got its answer: it sends its next request
            log.client.finished.get(timeout=max(0.0, t_end - now()))
        except queue.Empty:
            break
        if now() >= t_end:
            break
        log.send(nxt, *log.draw(nxt))
        nxt += 1
    return log.cancel_rest()


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--slots", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    a = ap.parse_args()
    with open(a.mix) as f:
        mix = json.load(f)

    client = _TimedClient("127.0.0.1", a.port)
    log = _Log(client, mix, a.seed, a.vocab)
    t0 = None

    def wait_go() -> float:
        nonlocal t0
        print("ready", flush=True)
        word, t = sys.stdin.readline().split()
        if word != "go":
            raise RuntimeError(f"expected 'go <t0>', got {word!r}")
        t0 = float(t)
        return t0

    try:
        if mix["loop"] == "open":
            rows = _open_loop(log, a.seconds, wait_go)
        elif mix["loop"] == "closed":
            rows = _closed_loop(log, a.seconds, a.slots, wait_go)
        else:
            raise ValueError(f"unknown loop {mix['loop']!r}")
    finally:
        client.close()
    # importing tpu_dist imports jax; what must not happen is a backend
    bridge = sys.modules.get("jax._src.xla_bridge")
    if bridge is not None and bridge.backends_are_initialized():
        raise RuntimeError("the load generator initialised a JAX backend")
    print(json.dumps({"t0": t0, "seconds": a.seconds, "requests": rows}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
