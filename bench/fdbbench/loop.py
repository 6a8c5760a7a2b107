"""The closed-loop traffic generator: writers and readers on one tree.

One general generator reads a traffic mix (``bench/traffic/<mix>.json``)
and a configuration's key space (``bench/configs/<config>.json``):

- each writer is the I/O server of one ensemble member.  A step is every
  parameter at every level of the configuration, archived in calls of
  ``fields_per_call`` fields (parameter-major) through ``archive_fields``;
  the call that ends a step also calls ``flush()``, and that call plus the
  flush is one request.  After ``steps_per_cycle`` steps a writer moves to
  a new forecast cycle (a new ``date`` dataset), and once it holds
  ``cycles`` cycles it wipes its oldest, as a hot tier rolls its
  retention.  Each writer owns its own datasets, so a wipe never takes
  another member's fields; wipes fall between requests.  With
  ``writers.window`` ``false`` (default ``true``) the writers archive their
  prefill in set-up, which readers and the read-back draw from, and start
  no thread in the window: a mix of readers alone over a finished step;
- each reader retrieves from the newest flushed step of a member (taken in
  turn, or drawn uniformly): one parameter at every level, or one field by
  exact key, through ``retrieve_fields(...).arrays()``.  A cycle is never
  wiped while a reader is inside it;
- every client waits for its reply before it sends the next request, and
  stops issuing once the window has elapsed, finishing the request in
  flight; a writer finishes the step in flight, flush included, so every
  byte it archived in the window is durable;
- a reader's answer has to carry exactly the keys it asked for.

Source fields come from a pool of ``pool_steps`` whole steps; writer
``w``'s ``n``-th step takes variant ``(w + n) % pool_steps``, rolled over
the flattened grid by a shift of its own (:meth:`Plan.shift`), so every
step of every member has sources no other step shares, and the source of
any key is known.  Decoded fields are sampled (a reservoir per reader,
drawn from the seed) for the comparison with the reference after the
window.
"""

from __future__ import annotations

import contextlib
import datetime
import math
import threading
import time
import traceback
from collections import Counter
from dataclasses import dataclass

import numpy as np

__all__ = ["Loop", "Plan", "Record"]

#: how long after the window closes a request may still come back (late,
#: not wrong); one that has not is a failure
GRACE_S = 120.0


@dataclass(frozen=True)
class Record:
    """One request: ``kind`` is ``archive``, ``retrieve`` (window),
    ``prefill``, ``warm`` or ``readback`` (outside the window)."""

    kind: str
    t0: float
    t1: float
    nbytes: int
    ok: bool


def _ident(key: dict) -> tuple:
    return tuple(sorted(key.items()))


def _matches(text: str, key: dict) -> bool:
    """``key=value[/value...]`` pairs joined by commas, all of which hold."""
    for part in text.split(","):
        kw, _, values = part.partition("=")
        if key.get(kw.strip()) not in values.strip().split("/"):
            return False
    return True


def tier_nbits(node: dict, key: dict) -> int:
    """The codec width of the tier the configuration routes ``key`` to
    (``select`` rules by match, else the default; ``codec`` nodes)."""
    kind = node.get("type")
    if kind == "select":
        for rule in node.get("rules", ()):
            if _matches(rule["match"], key):
                return tier_nbits(rule["fdb"], key)
        return tier_nbits(node["default"], key)
    if kind == "codec":
        return int(node["nbits"])
    raise ValueError(f"no codec tier on the route of {key} (node type {kind!r})")


class Plan:
    """A configuration's key space and a traffic mix's shape."""

    def __init__(self, config: dict, traffic: dict):
        self.grid = tuple(int(n) for n in config["grid"])
        self.dataset = {k: str(v) for k, v in config["dataset"].items()}
        self.first_date = datetime.datetime.strptime(config["first_date"], "%Y%m%d").date()
        self.params = {str(p): (float(b), float(s)) for p, (b, s) in config["params"].items()}
        self.levels = [str(lv) for lv in config["levels"]]
        self.tree = config["tree"]
        w, r, keep = traffic["writers"], traffic["readers"], traffic["retention"]
        self.writers = [int(m) for m in w["members"]]
        self.fields_per_call = int(w["fields_per_call"])
        #: whether the writers archive in the window, or only prefill
        self.write_in_window = w.get("window", True)
        self.steps_per_cycle = int(keep["steps_per_cycle"])
        self.cycles = int(keep["cycles"])
        self.n_readers = int(r["count"])
        self.member_pick = r["member"]
        self.request = r["request"]
        self.prefill_steps = int(traffic["prefill_steps"])
        self.pool_steps = int(traffic["pool_steps"])
        self.sample_fields = int(traffic["sample_fields"])
        self.readback = int(traffic["readback_per_member"])
        self.step_fields = [(p, lv) for p in self.params for lv in self.levels]
        if traffic.get("loop") != "closed":
            raise ValueError(f"the generator drives closed loops only, not {traffic.get('loop')!r}")
        if not set(self.writers) <= {int(m) for m in config["members"]}:
            raise ValueError(f"writers {self.writers} are not members of the configuration")
        if self.step_size % self.fields_per_call:
            raise ValueError(f"a step of {self.step_size} fields is not whole calls of "
                             f"{self.fields_per_call}")
        if not isinstance(self.write_in_window, bool):
            raise ValueError(f"writers.window is true or false, not {self.write_in_window!r}")
        if not (self.write_in_window or self.n_readers):
            raise ValueError("a window with neither writers nor readers measures nothing")
        if self.member_pick not in ("alternate", "uniform"):
            raise ValueError(f"unknown reader member pick {self.member_pick!r}")
        if self.request not in ("param_levels", "one_field"):
            raise ValueError(f"unknown reader request {self.request!r}")
        if self.cycles < 2 or not 1 <= self.prefill_steps <= self.steps_per_cycle:
            raise ValueError("retention needs 2 cycles or more, and prefill within one cycle")
        n = self.grid[0] * self.grid[1]
        # a fifth of the globe in latitude and a third in longitude a step,
        # coprime with the grid so that no two steps share a shift
        self._stride = (self.grid[0] // 5) * self.grid[1] + self.grid[1] // 3 + 1
        while math.gcd(self._stride, n) != 1:
            self._stride += 1

    @property
    def step_size(self) -> int:
        return len(self.step_fields)

    @property
    def field_bytes(self) -> int:
        return self.grid[0] * self.grid[1] * 4

    def pool_spread(self) -> tuple[list[float], list[float]]:
        """Base and spread of every pool slot, by the parameter it holds."""
        ps = [self.params[p] for p, _ in self.step_fields] * self.pool_steps
        return [b for b, _ in ps], [s for _, s in ps]

    def date(self, writer: int, cycle: int) -> str:
        day = self.first_date + datetime.timedelta(days=1000 * writer + cycle)
        return day.strftime("%Y%m%d")

    def key(self, member: int, date: str, step: int, param: str, level: str) -> dict:
        return {**self.dataset, "date": date, "number": str(member), "step": str(step),
                "param": param, "levelist": level}

    def slot(self, variant: int, param: str, level: str) -> int:
        return (variant * self.step_size
                + list(self.params).index(param) * len(self.levels) + self.levels.index(level))

    def shift(self, writer: int, done: int) -> int:
        """The roll, over the flattened grid, of writer ``writer``'s
        ``done``-th step: distinct for every step of every writer."""
        return (writer + len(self.writers) * done) * self._stride % (self.grid[0] * self.grid[1])

    def source(self, fields: np.ndarray, shift: int) -> np.ndarray:
        """``fields`` (``(F, H, W)``) rolled by ``shift`` over the grid."""
        flat = fields.reshape(len(fields), -1)
        return np.roll(flat, shift, axis=1).reshape(fields.shape)

    def request_for(self, member: int, date: str, step: int, rng) -> tuple[dict, list[dict]]:
        """A reader's request into one flushed step, and the keys it names."""
        param = list(self.params)[rng.integers(len(self.params))]
        if self.request == "param_levels":
            req = self.key(member, date, step, param, "")
            req["levelist"] = list(self.levels)
            return req, [self.key(member, date, step, param, lv) for lv in self.levels]
        key = self.key(member, date, step, param, self.levels[rng.integers(len(self.levels))])
        return key, [key]


class Loop:
    """Drive one traffic mix against one FDB tree (see module docstring).

    ``annotate(name)`` returns a context manager put around each call into
    the tree (``bench.archive``, ``bench.flush``, ``bench.retrieve``,
    ``bench.wipe``); by default it does nothing."""

    def __init__(self, fdb, plan: Plan, pool: np.ndarray, seed: int):
        self.fdb = fdb
        self.plan = plan
        self.pool = pool
        self.seed = int(seed) % (1 << 64)
        self.annotate = lambda name: contextlib.nullcontext()
        self._mu = threading.Condition()
        self._cursor: dict[int, tuple[int, int, int]] = {}   # member -> cycle, step, steps done
        self._newest: dict[int, tuple[str, int]] = {}        # member -> newest flushed step
        self._flushed: dict[int, list[tuple[str, int]]] = {}  # member -> retained flushed steps
        self._variant: dict[tuple[int, str, int], tuple[int, int]] = {}  # -> variant, shift
        self._reading: Counter = Counter()                    # (member, date) -> readers inside
        self.records: list[Record] = []
        #: ``(slot, shift, nbits, decoded)`` of sampled fields, window and
        #: read-back
        self.samples: list[tuple[int, int, int, np.ndarray]] = []
        self.failures: list[str] = []

    # ---------------------------------------------------------------- records
    def _record(self, rec: Record) -> None:
        with self._mu:
            self.records.append(rec)

    def _fail(self, what: str) -> None:
        with self._mu:
            self.failures.append(what)

    def window_records(self, kind: str) -> list[Record]:
        return [r for r in self.records if r.kind == kind]

    # ----------------------------------------------------------------- writer
    def _wipe(self, member: int, writer: int, cycle: int) -> None:
        date = self.plan.date(writer, cycle)
        with self._mu:
            if not self._mu.wait_for(lambda: self._reading[member, date] == 0, timeout=120):
                self.failures.append(f"readers stayed in {date} of member {member}")
                return
            self._flushed[member] = [(d, s) for d, s in self._flushed[member] if d != date]
        try:
            with self.annotate("bench.wipe"):
                self.fdb.wipe({**self.plan.dataset, "date": date})
        except Exception:  # noqa: BLE001 -- a failed operation is counted, not fatal
            self._fail(f"wipe of {date}:\n{traceback.format_exc()}")

    def _write_step(self, member: int, writer: int, kind: str) -> None:
        p = self.plan
        cycle, step, done = self._cursor[member]
        if step == p.steps_per_cycle:
            cycle, step = cycle + 1, 0
            if cycle >= p.cycles:
                self._wipe(member, writer, cycle - p.cycles)
        date = p.date(writer, cycle)
        variant, shift = (writer + done) % p.pool_steps, p.shift(writer, done)
        with self._mu:
            self._variant[member, date, step] = variant, shift
        keys = [p.key(member, date, step, prm, lv) for prm, lv in p.step_fields]
        base, fpc = variant * p.step_size, p.fields_per_call
        all_ok = True
        for lo in range(0, p.step_size, fpc):
            last = lo + fpc == p.step_size
            fields = p.source(self.pool[base + lo:base + lo + fpc], shift)
            t0 = time.perf_counter()
            try:
                with self.annotate("bench.archive"):
                    self.fdb.archive_fields(keys[lo:lo + fpc], fields)
                if last:
                    with self.annotate("bench.flush"):
                        self.fdb.flush()
                ok = True
            except Exception:  # noqa: BLE001
                ok = False
                self._fail(f"{kind} of member {member} {date} step {step}:\n{traceback.format_exc()}")
            t1 = time.perf_counter()
            self._record(Record(kind, t0, t1, fpc * p.field_bytes if ok else 0, ok))
            all_ok = all_ok and ok
        self._cursor[member] = (cycle, step + 1, done + 1)
        if all_ok:
            with self._mu:
                self._newest[member] = (date, step)
                self._flushed[member].append((date, step))

    # ----------------------------------------------------------------- reader
    def _read(self, member: int, date: str, step: int, rng, kind: str):
        """One reader request; returns the decoded fields with their keys,
        or ``None`` when the request failed or answered other keys than it
        named."""
        p = self.plan
        req, want = p.request_for(member, date, step, rng)
        n = len(want)
        t0 = time.perf_counter()
        out = None
        try:
            with self.annotate("bench.retrieve"):
                fs = self.fdb.retrieve_fields(req)
                arr = fs.arrays()
            got = [dict(k) for k in fs.keys]
            if sorted(map(_ident, got)) != sorted(map(_ident, want)):
                self._fail(f"{kind} {req} answered keys {got}")
            elif arr.shape != (n, *p.grid):
                self._fail(f"{kind} {req} gave shape {arr.shape}, expected {(n, *p.grid)}")
            else:
                out = (got, arr)
        except Exception:  # noqa: BLE001
            self._fail(f"{kind} {req}:\n{traceback.format_exc()}")
        t1 = time.perf_counter()
        self._record(Record(kind, t0, t1, n * p.field_bytes if out else 0, out is not None))
        return out

    def _sample_of(self, key: dict, decoded: np.ndarray) -> tuple[int, int, int, np.ndarray]:
        with self._mu:
            variant, shift = self._variant[int(key["number"]), key["date"], int(key["step"])]
        slot = self.plan.slot(variant, key["param"], key["levelist"])
        return slot, shift, tier_nbits(self.plan.tree, key), np.array(decoded, copy=True)

    def _reader(self, r: int, deadline: float, go: threading.Event) -> None:
        p = self.plan
        rng = np.random.default_rng([self.seed, 1, r])
        pick = np.random.default_rng([self.seed, 2, r])
        cap = -(-p.sample_fields // p.n_readers)
        reservoir: list = []
        seen = 0
        go.wait()
        i = 0
        while time.perf_counter() < deadline:
            if p.member_pick == "alternate":
                member = p.writers[(r + i) % len(p.writers)]
            else:
                member = p.writers[rng.integers(len(p.writers))]
            i += 1
            with self._mu:
                date, step = self._newest[member]
                self._reading[member, date] += 1
            try:
                got = self._read(member, date, step, rng, "retrieve")
            finally:
                with self._mu:
                    self._reading[member, date] -= 1
                    self._mu.notify_all()
            if got is None:
                continue
            for key, field in zip(*got):
                seen += 1
                if len(reservoir) < cap:
                    reservoir.append(self._sample_of(key, field))
                else:
                    j = int(pick.integers(seen))
                    if j < cap:
                        reservoir[j] = self._sample_of(key, field)
        with self._mu:
            self.samples.extend(reservoir)

    def _writer(self, member: int, writer: int, deadline: float, go: threading.Event) -> None:
        go.wait()
        while time.perf_counter() < deadline:
            self._write_step(member, writer, "archive")

    # ------------------------------------------------------------------ phases
    def prefill(self) -> None:
        """``prefill_steps`` flushed steps of every writer's member, archived
        in the traffic's own calls: what readers need, and the warm-up of
        every pack program."""
        for w, member in enumerate(self.plan.writers):
            self._cursor[member] = (0, 0, 0)
            self._flushed[member] = []
            for _ in range(self.plan.prefill_steps):
                self._write_step(member, w, "prefill")
            if member not in self._newest:
                raise RuntimeError(f"prefill of member {member} failed:\n" + "\n".join(self.failures))

    def warm_reads(self) -> None:
        """One reader request into every member: the warm-up of every unpack
        program and read path the window uses."""
        rng = np.random.default_rng([self.seed, 4])
        for member in self.plan.writers:
            date, step = self._newest[member]
            if self._read(member, date, step, rng, "warm") is None:
                raise RuntimeError(f"warm-up read of member {member} failed:\n"
                                   + "\n".join(self.failures))

    def run(self, seconds: float) -> None:
        """The measured window: every client on its own thread, issuing
        until ``seconds`` have elapsed.  A client that has not returned
        :data:`GRACE_S` after the close is a failure."""
        p = self.plan
        go = threading.Event()
        deadline = time.perf_counter() + seconds
        writers = enumerate(p.writers) if p.write_in_window else ()
        threads = [threading.Thread(target=self._writer, args=(m, w, deadline, go), daemon=True,
                                    name=f"bench-writer-{m}") for w, m in writers]
        threads += [threading.Thread(target=self._reader, args=(r, deadline, go), daemon=True,
                                     name=f"bench-reader-{r}") for r in range(p.n_readers)]
        for t in threads:
            t.start()
        go.set()
        for t in threads:
            t.join(timeout=max(0.0, deadline + GRACE_S - time.perf_counter()))
        stuck = [t.name for t in threads if t.is_alive()]
        if stuck:
            self._fail(f"clients still waiting {GRACE_S} s after the window closed: {stuck}")

    def readback(self) -> None:
        """After the window: read back a sample (drawn from the seed) of
        every writer's acknowledged steps that retention still holds, and
        keep every field read for the comparison."""
        rng = np.random.default_rng([self.seed, 3])
        for member in self.plan.writers:
            steps = self._flushed[member]
            for _ in range(self.plan.readback):
                date, step = steps[rng.integers(len(steps))]
                got = self._read(member, date, step, rng, "readback")
                if got is not None:
                    self.samples.extend(self._sample_of(k, f) for k, f in zip(*got))
