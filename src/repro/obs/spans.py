"""Span timers and counters, zero-cost when disabled, with a thread-safe
collector.

The runtime's hot paths are annotated with ``with span("solver/two_step")``
blocks and ``count("round/rows", n)`` calls; when the module flag is off
(the default) each is a single flag check: no import, no clock read, no
lock, no allocation beyond the span object itself.  `enable()` turns
every span in the process into a wall-clock measurement recorded in one
in-process collector keyed by span name (`totals()`), and every counter
into a running sum (`counters()`).  An enabled span also enters a
``jax.profiler.TraceAnnotation`` of its name, so while a profiler trace
is being taken it lands on the calling thread's host timeline beside
the device ops; the parent span of a block carries the block's round
cursor as trace metadata (``cursor``).

Span names in the runtime (all host-side; the per-round ones sit inside
the hierarchical loop, whose rounds are host work anyway):

  ==========================  ==============================================
  ``setup/experiment``        whole scheme setup (`Experiment.__init__`)
  ``solver/two_step``         two-step load-allocation solve
  ``encode/parity``           parity encode and aggregate, ending in a
                              sync on the parity set
  ``model/init``              a model spec's weights and AdamW state made
                              on the device (`repro.core.lm_round`),
                              ending in a sync on them
  ``trace/generate``          channel-trace block generation
  ``block/run``               one flat `run_block` (``cursor``)
  ``block/prepare``           its delay draws, scan inputs, fault rows
                              and consts, and one packed upload, before
                              dispatch
  ``scan/compile``            first (compiling) call of a cached scan,
                              synced
  ``scan/execute``            warm calls of that scan: the dispatch only
  ``block/fetch``             per-round outputs to the host: the wait
                              for the scan plus one packed copy
  ``block/state``             the run history grown and the new state
  ``checkpoint/save``         `save_state` (atomic npz write)
  ``checkpoint/restore``      `restore_state` (load + digest verify)
  ``hier/shard_setup``        one edge aggregator's deployment setup
  ``hier/round_block``        one hierarchical `run_block` (``cursor``)
  ``hier/sample``             its delay and cohort draws
  ``hier/shard_upload``       one shard's block and return mask to the
                              device, per round
  ``hier/shard_round``        one shard's round dispatch, per round
  ``journal/append``          run-journal block append
  ``service/block``           one `ExperimentService` block advance
  ``service/ckpt_save``       the service's view of one checkpoint save
  ``service/backoff``         retry backoff sleeps
  ==========================  ==============================================

No span forces a device sync except ``scan/compile`` (once per cached
scan) and ``encode/parity`` (set-up); device time is read from a
profiler trace, not from these clocks.

Counters (``{name: {"events", "total"}}``):

  ==========================  ==============================================
  ``round/rows``              rows the compiled round reads, per round:
                              the rows of the step's gradient tensor, the
                              parity set included (flat single-trajectory
                              blocks), or each shard's n_s * l client rows
                              plus its u_s parity rows (hierarchical)
  ``block/transfers``         host-device transfers of a flat block's
                              per-call inputs and outputs: 2 per call (one
                              packed upload, one packed fetch)
  ``hier/h2d_bytes``          bytes of the host arrays a hierarchical round
                              hands to the device (shard blocks and return
                              masks, as f32)
  ``round/tokens``            tokens a model round computes: every
                              client's whole sequence (n S a round)
  ``moe/routed``              token-expert assignments to the held experts
                              (dropless MoE), from the packed fetch
  ``moe/expert_rows``         rows the held experts' grouped matmuls
                              compute, each group in whole kernel tiles
  ``attn/scores``             query-key scores a model round's attention
                              forward computes, per sequence and layer
                              (heads not counted): the causal blocks
                              it visits, padding included
  ``attn/causal_scores``      the causal triangle over the same
                              sequences and layers, S (S + 1) / 2 each
  ==========================  ==============================================

Timing never touches an RNG stream or any value that flows into a
trajectory — runs with spans enabled are bit-identical to runs with spans
disabled (pinned by tests/test_obs.py).
"""
from __future__ import annotations

import contextlib
import json
import threading
import time

__all__ = ["span", "enable", "disable", "enabled", "reset", "record",
           "totals", "count", "counters", "write_json", "collecting",
           "SPANS_NAME"]

#: filename `write_json` conventionally uses inside a run directory
SPANS_NAME = "spans.json"

_enabled = False
_lock = threading.Lock()
#: name -> [count, total_s, min_s, max_s]
_records: "dict[str, list]" = {}
#: name -> [events, total]
_counters: "dict[str, list]" = {}
#: `jax.profiler.TraceAnnotation`, imported by the first enabled span
_annotation = None


def enabled() -> bool:
    """Whether spans currently measure (module-global, process-wide)."""
    return _enabled


def enable() -> None:
    """Turn every `span` in the process into a recorded measurement."""
    global _enabled
    _enabled = True


def disable() -> None:
    """Return spans to their zero-overhead pass-through behavior."""
    global _enabled
    _enabled = False


def reset() -> None:
    """Drop all collected records and counters (the enable flag is left
    as is)."""
    with _lock:
        _records.clear()
        _counters.clear()


def record(name: str, seconds: float) -> None:
    """Fold one measured duration into the collector (thread-safe)."""
    with _lock:
        rec = _records.get(name)
        if rec is None:
            _records[name] = [1, seconds, seconds, seconds]
        else:
            rec[0] += 1
            rec[1] += seconds
            if seconds < rec[2]:
                rec[2] = seconds
            if seconds > rec[3]:
                rec[3] = seconds


def totals() -> dict:
    """Snapshot the collector: {name: {count, total_s, min_s, max_s}},
    names sorted so the snapshot serializes deterministically."""
    with _lock:
        return {name: {"count": int(rec[0]), "total_s": float(rec[1]),
                       "min_s": float(rec[2]), "max_s": float(rec[3])}
                for name, rec in sorted(_records.items())}


def count(name: str, value) -> None:
    """Add `value` to counter `name` when spans are enabled (thread-safe);
    a single flag check otherwise."""
    if not _enabled:
        return
    with _lock:
        rec = _counters.get(name)
        if rec is None:
            _counters[name] = [1, value]
        else:
            rec[0] += 1
            rec[1] += value


def counters() -> dict:
    """Snapshot the counters: {name: {events, total}}, names sorted."""
    with _lock:
        return {name: {"events": int(rec[0]), "total": rec[1]}
                for name, rec in sorted(_counters.items())}


def _trace_annotation():
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def write_json(path: str) -> str:
    """Write `totals()` as pretty JSON (a run dir's ``spans.json``)."""
    with open(path, "w") as fh:
        json.dump(totals(), fh, indent=2, sort_keys=True)
        fh.write("\n")
    return path


class span:
    """``with span("solver/two_step"): ...`` — wall-clock one region.

    When the module flag is off the context manager is inert (no clock
    read, no trace annotation).  When it is on, the region also enters a
    ``jax.profiler.TraceAnnotation`` of the span's name, with ``cursor``
    (a block's first round) as its metadata when given.  ``force=True``
    measures regardless of the flag — the duration lands in
    ``self.elapsed_s`` for the caller, but is only folded into the global
    collector, and annotated, when the flag is on (the `ExperimentService`
    uses this for its always-on per-run health timings).
    """
    __slots__ = ("name", "elapsed_s", "_t0", "_force", "_cursor", "_ann")

    def __init__(self, name: str, *, force: bool = False,
                 cursor: "int | None" = None):
        self.name = name
        self.elapsed_s = None
        self._t0 = None
        self._force = force
        self._cursor = cursor
        self._ann = None

    def __enter__(self) -> "span":
        if _enabled:
            ann = _trace_annotation()
            self._ann = (ann(self.name) if self._cursor is None
                         else ann(self.name, cursor=int(self._cursor)))
            self._ann.__enter__()
            self._t0 = time.perf_counter()
        elif self._force:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self._t0 is not None:
            self.elapsed_s = time.perf_counter() - self._t0
            self._t0 = None
            if _enabled:
                record(self.name, self.elapsed_s)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        return False


@contextlib.contextmanager
def collecting(fresh: bool = True):
    """Enable spans for the duration of the block, restoring the previous
    flag afterwards; ``fresh`` clears the collector and the counters
    first.  Yields the
    module so ``with collecting() as spans: ... spans.totals()`` reads
    naturally."""
    global _enabled
    prev = _enabled
    if fresh:
        reset()
    _enabled = True
    try:
        yield __import__(__name__, fromlist=["totals"])
    finally:
        _enabled = prev
