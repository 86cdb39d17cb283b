"""A load-generator process: one bridge connection, driven by the mix's
traffic generator (``benchmark.traffic``, or the module a mix names). It
never imports JAX (the parent holds the chip).

The parent writes commands to stdin, one per line, and reads one JSON
event per line from stdout:

  (start)                 -> {"event": "built", ...}   set-up frames made
  "connect <port>"        -> {"event": "setup", ...}   resident sessions in
  "warm"                  -> {"event": "warm", ...}    warm-up traffic done
  "go <t0>"               -> {"event": "window", ...}  the measured window
  "query"                 -> {"event": "queried"}      end state read back
  "reference"             -> {"event": "reference", ...} the comparison

The first stdin line is the JSON job: seed, conn, config, mix, seconds.
The warm-up and window frames are made in a thread of their own while the
resident proposals go out, so that vote signing overlaps the server's
proposal intake.
"""

from __future__ import annotations

import json
import sys
import threading
import time

import numpy as np

from . import plugins, reference, traffic, wire


def emit(**event) -> None:
    sys.stdout.write(json.dumps(event) + "\n")
    sys.stdout.flush()


class Runner:
    """Sends frames on one connection and records every reply."""

    def __init__(self, conn: wire.Connection, inflight: int):
        self.conn = conn
        self.slots = threading.Semaphore(inflight)
        self.lock = threading.Lock()
        self.replies: "dict[int, tuple]" = {}  # corr -> (t, status, payload)
        self.error: "Exception | None" = None
        self.outstanding = 0
        self.done = threading.Condition(self.lock)
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def _read(self) -> None:
        try:
            while True:
                status, corr, payload = self.conn.recv()
                t = time.monotonic()
                with self.lock:
                    self.replies[corr] = (t, status, payload)
                    self.outstanding -= 1
                    self.done.notify_all()
                self.slots.release()
        except Exception as exc:  # connection closed or broken
            with self.lock:
                self.error = exc
                self.done.notify_all()

    def send(self, corr: int, frame, block: bool = True) -> float:
        if block:
            self.slots.acquire()
        with self.lock:
            self.outstanding += 1
        op = wire.OP_VOTE_BATCH if frame.kind == "votes" else \
            wire.OP_DELIVER_PROPOSALS
        t = time.monotonic()
        self.conn.send(op, corr, frame.payload)
        return t

    def drain(self, timeout: float) -> bool:
        end = time.monotonic() + timeout
        with self.lock:
            while self.outstanding > 0 and self.error is None:
                left = end - time.monotonic()
                if left <= 0:
                    return False
                self.done.wait(left)
            return self.outstanding == 0


class Job:
    def __init__(self, spec: dict):
        self.cfg, self.mix = spec["config"], spec["mix"]
        self.seed, self.seconds = spec["seed"], spec["seconds"]
        gen = (plugins.load_module("traffic", self.mix["generator"])
               if "generator" in self.mix else traffic)
        self.plan = gen.Plan(self.seed, self.cfg, self.mix, spec["conn"],
                             peer=spec["peer"])
        self.frames: list = []  # every frame sent, in order
        self.sent: "list[float]" = []

    def build(self) -> None:
        self.setup = self.plan.setup_frames()
        self.maker = threading.Thread(target=self._make, daemon=True)
        self.maker.start()
        emit(event="built", frames=len(self.setup))

    def _make(self) -> None:
        t0 = time.monotonic()
        self.warm = self.plan.warm_frames()
        self.window = self.plan.window_frames()
        self.gen_s = time.monotonic() - t0

    def _send_all(self, runner: Runner, frames, start: float,
                  stop: "float | None" = None) -> "list[float]":
        """Send ``frames`` in order: a frame with a ``due`` time at ``start
        + due`` (open loop), any other as the in-flight limit allows
        (closed loop), until ``stop``. Returns the open-loop lateness."""
        late = []
        for f in frames:
            if f.due is not None:
                wait = start + f.due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
            if stop is not None and time.monotonic() >= stop:
                break
            corr = len(self.frames)
            self.frames.append(f)
            t = runner.send(corr, f, block=f.due is None)
            self.sent.append(t)
            if f.due is not None:
                late.append(t - (start + f.due))
        return late

    def connect(self, port: int) -> None:
        self.conn = wire.Connection("127.0.0.1", port)
        self.runner = Runner(self.conn, self.mix["inflight"])
        self._send_all(self.runner, self.setup, 0.0)
        ok = self.runner.drain(300.0)
        emit(event="setup", ok=ok, frames=len(self.setup))

    def warmup(self) -> None:
        self.maker.join()
        self._send_all(self.runner, self.warm, time.monotonic() + 0.01)
        ok = self.runner.drain(300.0)
        emit(event="warm", ok=ok, frames=len(self.warm), gen_s=self.gen_s,
             window_votes=getattr(self.plan, "pregenerated_votes", 0))

    def run_window(self, t0: float) -> None:
        t1 = t0 + self.seconds
        first = len(self.frames)
        wait = t0 - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        late = self._send_all(self.runner, self.window, t0, stop=t1)
        self.runner.drain(60.0)
        replies = self.runner.replies
        votes_in = attempted = failed = frames_in = 0
        lat: "list[float]" = []
        for corr in range(first, len(self.frames)):
            f = self.frames[corr]
            n = len(f.sids) if f.kind == "votes" else len(f.items)
            attempted += n
            got = replies.get(corr)
            if got is None or got[1] != 0:
                failed += n
                continue
            t_reply = got[0]
            if t_reply > t1:
                continue
            frames_in += 1
            if f.kind != "votes":
                continue
            votes_in += n
            if f.deciding:
                start = self.sent[corr] if f.due is None else t0 + f.due
                lat.extend([t_reply - start] * f.deciding)
        emit(event="window", votes_in_window=votes_in,
             frames_in_window=frames_in, attempted=attempted, failed=failed,
             lazy_frames=getattr(self.plan, "lazy_frames", 0),
             decide_latencies_s=lat,
             late_p50_s=float(np.median(late)) if late else 0.0,
             late_p99_s=float(np.quantile(late, 0.99)) if late else 0.0,
             late_max_s=float(max(late)) if late else 0.0)

    def query(self) -> None:
        """Read back the end state of every session the reference replays."""
        self.results = {}
        runner = self.runner
        corr0 = len(self.frames)
        asked = []
        for sess in self.plan.registry:
            if isinstance(sess, tuple):
                continue
            corr = corr0 + len(asked)
            runner.slots.acquire()
            with runner.lock:
                runner.outstanding += 1
            self.conn.send(wire.OP_GET_RESULT, corr, wire.get_result_payload(
                self.plan.peer, traffic.scope_name(sess.scope), sess.pid))
            asked.append((corr, sess))
        runner.drain(120.0)
        for corr, sess in asked:
            got = runner.replies.get(corr)
            if got is None:
                self.results[(sess.scope, sess.pid)] = None
            elif got[1] != 0:
                self.results[(sess.scope, sess.pid)] = got[1] << 8
            else:
                self.results[(sess.scope, sess.pid)] = got[2][0]
        emit(event="queried", sessions=len(asked))

    def check(self) -> None:
        """Replay every frame through the plain reference, in send order,
        and compare what the server answered."""
        cfg = self.cfg
        t_ref = time.monotonic()
        node = reference.Node(cfg["network"], cfg["threshold"],
                              cfg["max_sessions_per_scope"],
                              self.plan.voters.scheme.verify)
        n, live = cfg["expected_voters"], cfg["liveness_criteria_yes"]
        ids = self.plan.voters.ids
        reg = self.plan.registry
        counts = dict(status_mismatches=0, proposal_mismatches=0,
                      result_mismatches=0, unanswered_frames=0,
                      votes_compared=0, tampered_rejected=0,
                      sessions_compared=0, sessions_decided=0)
        first_bad = []
        replies = self.runner.replies
        for corr, f in enumerate(self.frames):
            got = replies.get(corr)
            if got is None:
                counts["unanswered_frames"] += 1
                continue
            if got[1] != 0:
                continue  # refused whole (e.g. retry-after): not applied
            try:
                codes = wire.statuses_of(got[2])
            except ValueError:
                counts["unanswered_frames"] += 1
                continue
            if f.kind == "deliver":
                for j, (s, k) in enumerate(f.items):
                    want = node.deliver(traffic.scope_name(s),
                                        traffic.pid_of(s, k), n, live,
                                        traffic.BASE_TIME,
                                        traffic.BASE_TIME + traffic.EXPIRY,
                                        f.now)
                    if codes[j] != want:
                        counts["proposal_mismatches"] += 1
                continue
            for t in range(len(f.sids)):
                sess = reg[f.sids[t]]
                if isinstance(sess, tuple):
                    continue
                i = int(f.idx[t])
                want = node.vote(
                    traffic.scope_name(sess.scope), sess.pid,
                    ids[int(sess.voters[i])], bool(sess.values[i]),
                    traffic.BASE_TIME, int(sess.vote_ids[i]), b"",
                    b"", sess.hashes[i], sess.sigs[i], f.now)
                counts["votes_compared"] += 1
                if want == reference.INVALID_VOTE_SIGNATURE \
                        and codes[t] == want:
                    counts["tampered_rejected"] += 1
                if codes[t] != want:
                    counts["status_mismatches"] += 1
                    if len(first_bad) < 5:
                        first_bad.append([sess.scope, sess.k, i, codes[t],
                                          want])
        for (scope, pid), got in getattr(self, "results", {}).items():
            want = node.result(traffic.scope_name(scope), pid)
            counts["sessions_compared"] += 1
            counts["sessions_decided"] += want in (wire.RESULT_YES,
                                                   wire.RESULT_NO)
            if got != want:
                counts["result_mismatches"] += 1
        emit(event="reference", reference_s=time.monotonic() - t_ref,
             first_mismatches=first_bad, **counts)


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    job = Job(spec)
    job.build()
    for line in sys.stdin:
        cmd = line.split()
        if not cmd:
            continue
        if cmd[0] == "connect":
            job.connect(int(cmd[1]))
        elif cmd[0] == "warm":
            job.warmup()
        elif cmd[0] == "go":
            job.run_window(float(cmd[1]))
        elif cmd[0] == "query":
            job.query()
        elif cmd[0] == "reference":
            job.check()
        elif cmd[0] == "close":
            job.conn.close()
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
