"""A second process beside a benchmark run that says, of a stall in the
run, which of three kinds it was. Not part of a benchmark run (the driver
never calls it); the builder's tool for `serve-chat`'s stall (PERF.md).

    python3 benchmark/tools/stall_sampler.py --out FILE -- <command> ...

It starts the command as its child (it never touches JAX itself, so the
child gets the chip), and every ``--period`` seconds (10 ms) reads
``/proc/<pid>/task/*/schedstat`` of the child: for every thread the
nanoseconds it has run, the nanoseconds it has waited RUNNABLE for a core,
and the timeslices it has had. The child's main thread (tid == pid) is the
benchmark's own: the generator, the client's reads and the runner's loop.
A kernel built without scheduler statistics has no such file (the chip's
machines: PERF.md, PR 49); there the same three numbers are made from
``/proc/<pid>/task/*/stat`` (the thread's state letter and its CPU time in
clock ticks: runnable-wait is the time it was seen ``R`` while its CPU time
stood still) and, for the main thread, the context switches of its
``status``. Beside them every sample reads the first line of ``/proc/stat``:
the machine's busy time and its STEAL, the time its virtual CPUs stood
ready and were given no real one.

From the samples it cuts EPISODES: stretches of ``--least`` seconds
(0.08) or more in which the main thread was in one state other than its
polling (which sleeps 2 ms at a time, so has several timeslices a sample;
a sample's state is read over the ``SMOOTH`` samples up to it, since CPU
time in ticks of 10 ms says little over one):

- ``running``: its run time grew by 70% of the wall time or more: the
  thread's own work (a collection, a span ring, a long loop);
- ``waiting``: its runnable-wait grew by half the wall time or more: it
  wanted a core and got none (the machine; the sampler's own hold-ups in
  the same stretch, ``sampler_gap_max_s``, say whether it starved too);
- ``blocked``: no timeslice and no run time: asleep on something. Where
  another thread of the process ran through the stretch it holds what the
  main thread waits for (the GIL, a lock): ``top`` names the threads whose
  run time grew most, by tid and ``comm``; where none ran and the sampler
  was held up too, the whole machine stood still.

The record (``--out``, one JSON object) holds the episodes with their
times on ``time.monotonic()`` (the clock of ``time.perf_counter()`` on
Linux, so the run's own stamps lie on it), the sampler's own hold-ups
(``sample``: a sleep that came back late, or a slow pass over the child's
files), and the counts. The
runner prints its window and the hold-ups its own loop saw on the same
clock (``bench stalls``); ``benchmark/tools/runs.py`` lays the two side by
side."""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

RUNNING, WAITING, BLOCKED, POLLING = "running", "waiting", "blocked", "polling"
SMOOTH = 5          # samples a state is read over
TICK_NS = 1_000_000_000 // os.sysconf("SC_CLK_TCK")
MACHINE = -1        # the key of the machine's own row in a sample


def classify(d_run: float, d_wait: float, d_slices: int, dt: float) -> str:
    """The main thread's state over one sample, from what grew in it
    (seconds run, seconds runnable and waiting, timeslices) and its
    length."""
    if dt <= 0:
        return POLLING
    if d_run >= 0.7 * dt:
        return RUNNING
    if d_wait >= 0.5 * dt:
        return WAITING
    if d_slices == 0 and d_run < 0.1 * dt:
        return BLOCKED
    return POLLING


def episodes(samples: list, pid: int, least_s: float, period: float) -> list:
    """Cut the episodes out of ``samples``: [(t, {tid: (run_ns, wait_ns,
    slices)})], oldest first."""
    out, start, state = [], None, POLLING

    def close(i_end):
        t_a, a = samples[start]
        t_b, b = samples[i_end]
        if t_b - t_a < least_s:
            return
        grew = sorted(((b[tid][0] - a[tid][0]) / 1e9, tid) for tid in b
                      if tid in a and tid not in (pid, MACHINE))
        gaps = [samples[i + 1][0] - samples[i][0]
                for i in range(start, i_end)]
        main_a, main_b = a[pid], b[pid]
        out.append({
            "kind": state, "start": t_a, "seconds": t_b - t_a,
            "main_run_s": (main_b[0] - main_a[0]) / 1e9,
            "main_wait_s": (main_b[1] - main_a[1]) / 1e9,
            "main_slices": main_b[2] - main_a[2],
            "others_run_s": sum(s for s, _ in grew),
            "top": [[tid, s] for s, tid in reversed(grew[-4:]) if s > 0],
            # the machine over the stretch: CPU-seconds busy and stolen
            "machine_busy_s": ((b[MACHINE][0] - a[MACHINE][0]) / 1e9
                               if MACHINE in a and MACHINE in b else None),
            "machine_steal_s": ((b[MACHINE][1] - a[MACHINE][1]) / 1e9
                                if MACHINE in a and MACHINE in b else None),
            "sampler_gap_max_s": max(gaps, default=0.0),
            "sampler_late": sum(g > 3 * period for g in gaps)})

    for i in range(1, len(samples)):
        (t0, a), (t1, b) = samples[max(0, i - SMOOTH)], samples[i]
        if pid not in a or pid not in b:
            now = POLLING
        else:
            now = classify((b[pid][0] - a[pid][0]) / 1e9,
                           (b[pid][1] - a[pid][1]) / 1e9,
                           b[pid][2] - a[pid][2], t1 - t0)
        if now != state:
            if state != POLLING and start is not None:
                close(i - 1)
            state, start = now, i - 1
    if state != POLLING and start is not None:
        close(len(samples) - 1)
    return out


class Tasks:
    """The child's threads, each with its file held open: ``schedstat``
    where the kernel has it, else ``stat`` (and the main thread's
    ``status``), from which the same three numbers are made."""

    def __init__(self, pid: int):
        self.pid, self.dir = pid, f"/proc/{pid}/task"
        self.fds: dict = {}
        self.comm: dict = {}
        self.source = ("schedstat" if os.path.exists(
            f"{self.dir}/{pid}/schedstat") else "stat")
        self.made: dict = {}        # stat: tid -> [ticks, wait_ns, slices, t]
        self.status = self.machine = None

    def refresh(self):
        try:
            tids = {int(t) for t in os.listdir(self.dir)}
        except OSError:
            return
        for tid in tids - self.fds.keys():
            try:
                self.fds[tid] = os.open(f"{self.dir}/{tid}/{self.source}",
                                        os.O_RDONLY)
                with open(f"{self.dir}/{tid}/comm") as f:
                    self.comm[tid] = f.read().strip()
            except OSError:
                pass
        for tid in self.fds.keys() - tids:
            os.close(self.fds.pop(tid))
        try:
            if self.machine is None:
                self.machine = os.open("/proc/stat", os.O_RDONLY)
            if self.status is None and self.source == "stat":
                self.status = os.open(f"{self.dir}/{self.pid}/status",
                                      os.O_RDONLY)
        except OSError:
            pass

    def _switches(self) -> int:
        """The main thread's context switches, of its own will and not."""
        text = os.pread(self.status, 4096, 0)
        return sum(int(line.split()[1]) for line in text.splitlines()
                   if line.startswith((b"voluntary_ctxt_switches",
                                       b"nonvoluntary_ctxt_switches")))

    def _from_stat(self, tid: int, raw: bytes, now: float) -> tuple:
        fields = raw[raw.rindex(b")") + 2:].split()
        # the fields behind the command's name: state, ..., utime, stime
        ticks = int(fields[11]) + int(fields[12])
        runnable = fields[0] == b"R"
        made = self.made.setdefault(tid, [ticks, 0, 0, now])
        if runnable and ticks == made[0]:
            made[1] += int((now - made[3]) * 1e9)   # wanted a core, had none
        if tid == self.pid and self.status is not None:
            made[2] = self._switches()
        elif runnable or ticks != made[0]:
            made[2] += 1
        made[0], made[3] = ticks, now
        return ticks * TICK_NS, made[1], made[2]

    def read(self, now: float) -> dict:
        out = {}
        for tid, fd in list(self.fds.items()):
            try:
                raw = os.pread(fd, 512, 0)
                if self.source == "stat":
                    out[tid] = self._from_stat(tid, raw, now)
                else:
                    a, b, c = raw.split()
                    out[tid] = (int(a), int(b), int(c))
            except (OSError, ValueError):
                os.close(self.fds.pop(tid))
        if out and self.machine is not None:
            # cpu user nice system idle iowait irq softirq steal ...
            f = [int(x) for x in os.pread(self.machine, 256, 0)
                 .split(b"\n", 1)[0].split()[1:9]]
            out[MACHINE] = ((sum(f) - f[3] - f[4]) * TICK_NS,
                            f[7] * TICK_NS, 0)
        return out

    def close(self):
        for fd in list(self.fds.values()) + [self.status, self.machine]:
            if fd is not None:
                os.close(fd)
        self.fds = {}


def sample(child: subprocess.Popen, period: float) -> tuple:
    """Sample until the child ends: (samples, comm by tid, the source of
    the threads' numbers, the sampler's own hold-ups). A hold-up is a
    sleep of ``period`` that came back two periods late or more, [when,
    seconds late], or a pass over the child's files that took as long,
    [when, seconds, "read"]: the first is the machine's doing, the second
    may be the child's (a reader of ``/proc/<pid>`` waits for locks the
    process holds)."""
    tasks, samples, held, n = Tasks(child.pid), [], [], 0
    while child.poll() is None:
        if n % 100 == 0:
            tasks.refresh()
        n += 1
        now = time.monotonic()
        row = tasks.read(now)
        read = time.monotonic()
        if row:
            samples.append((now, row))
        if read - now >= 2 * period:
            held.append([now, read - now, "read"])
        time.sleep(period)
        late = time.monotonic() - read - period
        if late >= 2 * period:
            held.append([read, late, "sleep"])
    tasks.close()
    return samples, tasks.comm, tasks.source, held


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--period", type=float, default=0.01)
    ap.add_argument("--least", type=float, default=0.08)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    command = args.command[1:] if args.command[:1] == ["--"] else args.command
    if not command:
        ap.error("no command")
    child = subprocess.Popen(command)
    try:
        samples, comm, source, held = sample(child, args.period)
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
    gaps = [samples[i + 1][0] - samples[i][0]
            for i in range(len(samples) - 1)]
    found = episodes(samples, child.pid, args.least, args.period)
    for e in found:
        e["top"] = [[tid, comm.get(tid, "?"), s] for tid, s in e["top"]]
    record = {"pid": child.pid, "period_s": args.period, "source": source,
              "samples": len(samples), "threads": len(comm),
              "span": [samples[0][0], samples[-1][0]] if samples else None,
              "sampler_held": held[:200], "sampler_held_count": len(held),
              "sampler_gap_max_s": max(gaps, default=0.0),
              "episodes": found}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f)
    return child.returncode


if __name__ == "__main__":
    sys.exit(main())
