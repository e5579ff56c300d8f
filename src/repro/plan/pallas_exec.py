"""Device-resident schedule execution: one jitted program per plan.

``run_schedule`` (``plan.pallas``) dispatches a :class:`PallasSchedule`
one step at a time from the host -- a device round-trip, a fresh weight
conversion, and a ``block_until_ready`` per kernel.  A PIM controller
pays none of that: weights are resident in the arrays, step results feed
successors directly, and the host sees one completion.  This module is
that execution model (DESIGN.md Sec. 15):

* :func:`compile_schedule` lowers an entire schedule -- every measured
  step plus its bp2bs/bs2bp repack -- into ONE jitted program, each
  step the one step function of ``plan.pallas``.  Weights are
  converted/packed once at *compile* time (``hold``) into a
  device-resident param pytree: BP steps hold int8 limb stacks
  ``[ceil(bits/7), K, N]`` (the form the BP kernel reads), BS-resident
  steps hold pre-packed ``[bits, K/32, N]`` planes.  Boundary repacks
  the plan charges stay *in* the program (``apply``): a ``bp2bs`` step
  keeps word-form params and packs in-flight (through the fused
  bitpack-matmul when the schedule fused it), a ``bs2bp`` step keeps
  plane-form params and unpacks (and splits into limbs) in-flight.
* Step results thread to successor activations along the Workload
  ``deps`` DAG (``kernels.ops.thread_activations``) -- real dataflow, so
  XLA cannot elide or reorder the chain, and synthetic operands exist
  only at entry steps.
* Entry activations are device-resident state of the executable, as
  the params are: placed once at compile time (no copy when the caller
  already passed device arrays) and read in place by every ``run()``,
  which fetches fresh results each call.  Nothing is donated: XLA
  aliases an input only into an output of the same shape and dtype, and
  entries are int8 ``[m, k]`` while the output is int32.
* Results leave the device in a few flat buffers: the program writes
  each step's int32 ``[m, n]`` result, as soon as the step is done, into
  a flat 1-D int32 buffer at a static offset (steps in order, no gaps;
  a new buffer starts where the next result would pass
  :data:`FETCH_CHUNK_BYTES`), and ``run()`` starts every buffer's copy
  before it waits on the first, then hands back each step's result as a
  view of its buffer.  One copy per step paid a host-side set-up and a
  layout conversion per array, in series; one copy of everything runs
  at a single copy's speed, which falls once a buffer passes a few tens
  of MB.  Writing in place (not one concatenate at the end) lets each
  step's padded kernel output die with its step, so the device holds no
  more than the result bytes.

Per-step ``run_schedule`` runs the same ``apply(s, x, hold(s, w))`` one
step at a time and stays authoritative as the differential reference:
with the same threading it is bit-exact with the chained program and
with the numpy ``reference_results`` (pinned by
``tests/test_pallas_exec.py``).

Executables are content-addressed (:class:`ExecutableCache`, the
``serve.plan_cache`` sha256 pattern) by canonical schedule dict + kernel
source fingerprint + seed + JAX backend platform -- in-memory only,
because an executable holds live jitted closures and device buffers.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from collections import OrderedDict
from typing import Any, Optional

import numpy as np

from repro import spans
from repro.plan.pallas import PallasSchedule, apply, hold, synth_inputs

#: results leave the device in flat int32 buffers of at most this many
#: bytes (a step's result is never split), every copy in flight at once:
#: on a TPU v5e host one copy of a ~26 MB result ran at ~8 GB/s, one of
#: ~100 MB or more at 2.3-3.4 GB/s, and overlapped copies of the same
#: bytes at ~11 GB/s (PERF.md, section 6)
FETCH_CHUNK_BYTES = 16 << 20

#: default :class:`ExecutableCache` capacity -- live executables are far
#: heavier than cached plans (jitted closures + device-resident params),
#: but one serve-bench traffic mix lowers to only a few dozen distinct
#: schedules under one execute budget
DEFAULT_CAPACITY = 64


def kernel_fingerprint() -> str:
    """Source fingerprint of the executor and every module that
    determines what a compiled schedule computes.

    The provenance rule of ``serve.plan_cache``: editing any of these
    must miss the executable cache, so the address hashes their source.
    """
    import repro.plan.pallas as pallas_mod
    import repro.plan.pallas_exec as exec_mod
    from repro.kernels import (bitpack, bitparallel_matmul, bitserial_matmul,
                               fused_bitserial_matmul, ops, tiling)
    from repro.util import source_fingerprint

    return source_fingerprint(
        exec_mod, pallas_mod, ops, tiling, bitpack, bitparallel_matmul,
        bitserial_matmul, fused_bitserial_matmul)


def schedule_key(schedule: PallasSchedule, *, seed: int = 0,
                 fingerprint: Optional[str] = None) -> str:
    """Content address of a compiled schedule: sha256 over the canonical
    schedule dict (steps, layouts, dims, repacks, deps, fuse_pack), the
    synth seed, the JAX backend platform (which decides interpreted or
    compiled kernels), and the kernel source fingerprint."""
    import jax

    blob = json.dumps(
        {"schedule": schedule.to_dict(), "seed": seed,
         "platform": jax.default_backend(),
         "fingerprint": fingerprint or kernel_fingerprint()},
        sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:24]


@dataclasses.dataclass
class ScheduleExecutable:
    """A :class:`PallasSchedule` compiled to one jitted device program.

    ``compile_us`` charges everything the steady state never pays again:
    operand synthesis, entry placement and weight conversion/packing
    into device residency (span ``schedule.pack``), tracing, XLA
    compilation, and the first (warming) execution (span
    ``schedule.first_run``).  ``run()`` is the warm path.
    """

    schedule: PallasSchedule
    key: str
    compile_us: float
    n_measured: int
    n_modelled: int
    entry_ops: tuple[str, ...]     #: steps consuming synthetic operands
    threaded: dict                 #: {consumer op: producer op}
    params_bytes: int              #: device-resident weight footprint
    entry_bytes: int               #: device-resident entry footprint
    _fn: Any = dataclasses.field(repr=False)
    _params: Any = dataclasses.field(repr=False)
    _entry: dict = dataclasses.field(repr=False)   #: resident entries
    #: {op: (buffer, offset, m, n)}: where each result sits in the
    #: program's flat output buffers
    _slots: dict = dataclasses.field(repr=False)
    runs: int = 0

    def run(self) -> dict:
        """Execute the whole chained program once; returns
        {op: int32 [m, n] numpy result} for every measured step.

        Entry activations are resident on the device and read in place:
        nothing is donated, so running twice is safe and bit-identical.
        The results are views of the program's flat output buffers,
        fetched with every copy in flight at once; each call's buffers
        are fresh.  Each call records the spans ``schedule.run`` >
        ``schedule.place`` / ``.dispatch`` / ``.wait`` / ``.fetch`` and
        the counters ``schedule.place_bytes`` (entry bytes moved
        host->device: 0 when every entry is resident) /
        ``schedule.fetch_arrays`` (device arrays fetched) /
        ``schedule.fetch_bytes`` (``repro.spans``).
        """
        import jax

        with spans.span("schedule.run", key=self.key, call=self.runs):
            with spans.span("schedule.place"):
                host = [v for v in self._entry.values()
                        if not isinstance(v, jax.Array)]
            spans.count("schedule.place_bytes", sum(v.nbytes for v in host))
            with spans.span("schedule.dispatch"):
                out = self._fn(self._entry, self._params)
            with spans.span("schedule.wait"):
                jax.block_until_ready(out)
            with spans.span("schedule.fetch"):
                host = jax.device_get(out)   # starts every copy first
                got = {op: host[b][o:o + m * n].reshape(m, n)
                       for op, (b, o, m, n) in self._slots.items()}
            spans.count("schedule.fetch_arrays", len(host))
            spans.count("schedule.fetch_bytes", sum(h.nbytes for h in host))
            self.runs += 1
        return got

    def summary(self) -> dict:
        return {"key": self.key, "workload": self.schedule.workload,
                "compile_us": self.compile_us,
                "n_measured": self.n_measured,
                "n_modelled": self.n_modelled,
                "entry_ops": list(self.entry_ops),
                "threaded": dict(self.threaded),
                "params_bytes": self.params_bytes,
                "entry_bytes": self.entry_bytes, "runs": self.runs}


def compile_schedule(schedule: PallasSchedule,
                     inputs: Optional[dict] = None, *, seed: int = 0,
                     key: Optional[str] = None) -> ScheduleExecutable:
    """Compile ``schedule`` into ONE jitted program (module doc).

    ``inputs``: optional ``{op: (x, w)}`` word-form operands (default:
    :func:`plan.pallas.synth_inputs` with ``seed``).  Entry activations
    that are already device arrays become the executable's resident
    entries as they are (no copy); the executable never deletes them,
    so the caller's arrays stay valid.  Weights must be
    canonical ``width``-bit words -- a boundary repack round-trips them
    through the plane form, which truncates any bits above ``width``
    (synthetic operands satisfy this by construction).
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    with spans.span("schedule.pack"):
        if inputs is None:
            inputs = synth_inputs(schedule, seed=seed)
        if key is None:
            key = schedule_key(schedule, seed=seed)
        producer = schedule.threaded_producers()
        steps = schedule.measured_steps

        # ---- compile-time residency: convert/pack every weight once ----
        params: dict[str, Any] = {}
        entry: dict[str, Any] = {}
        for s in steps:
            x, w = inputs[s.op]
            if s.op not in producer:
                entry[s.op] = jnp.asarray(x)
            params[s.op] = hold(s, w)
        # the span covers the placement and conversions, not only their
        # dispatch
        jax.block_until_ready((entry, params))

    # each result's place in the flat outputs, in step order
    slots: dict[str, tuple[int, int, int, int]] = {}
    sizes: list[int] = []      # elements of each flat output
    for s in steps:
        m, _k, n = s.dims
        if not sizes or (sizes[-1] + m * n) * 4 > FETCH_CHUNK_BYTES:
            sizes.append(0)
        slots[s.op] = (len(sizes) - 1, sizes[-1], m, n)
        sizes[-1] += m * n

    def program(xs, ps):
        out = {}
        bufs = [jnp.zeros((size,), jnp.int32) for size in sizes]
        for s in steps:
            m, k, _n = s.dims
            src = producer.get(s.op)
            # one scope per step: the step's device ops carry its op name
            with jax.named_scope(s.op):
                x = (kops.thread_activations(out[src], m, k)
                     if src is not None else xs[s.op])
                y = apply(s, x, ps[s.op])
            out[s.op] = y
            # in place, as soon as the step is done: its padded kernel
            # output is not kept alive to the end of the program
            b, o, _m, _n = slots[s.op]
            bufs[b] = jax.lax.dynamic_update_slice(
                bufs[b], y.reshape(-1), (o,))
        return tuple(bufs)

    fn = jax.jit(program)
    # build = trace + lower + compile + first (warming) run, on the same
    # resident buffers every run() reads
    with spans.span("schedule.first_run", key=key):
        jax.block_until_ready(fn(entry, params))
    compile_us = (spans.last("schedule.pack").dur_ns
                  + spans.last("schedule.first_run").dur_ns) / 1e3

    return ScheduleExecutable(
        schedule=schedule, key=key, compile_us=compile_us,
        n_measured=len(steps),
        n_modelled=len(schedule.steps) - len(steps),
        entry_ops=tuple(entry), threaded=producer,
        params_bytes=sum(int(np.prod(p.shape)) * p.dtype.itemsize
                         for p in params.values()),
        entry_bytes=sum(v.nbytes for v in entry.values()),
        _fn=fn, _params=params, _entry=entry, _slots=slots)


class ExecutableCache:
    """In-memory LRU of :class:`ScheduleExecutable`, content-addressed
    by :func:`schedule_key`.

    The serving steady state: every batch group whose representative
    lowers to an identical schedule (same steps, layouts, dims, repacks,
    deps) reuses one compiled program and its device-resident weights
    and entry operands (``entry_bytes`` each, held on the device).
    Unlike :class:`serve.plan_cache.PlanCache` there is no disk tier --
    an executable holds live jitted closures and device buffers, so the
    cache is per-process by nature; the source fingerprint still
    guarantees an edit to any kernel misses.
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 fingerprint: Optional[str] = None) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1 (got {capacity})")
        self.capacity = capacity
        self.fingerprint = fingerprint or kernel_fingerprint()
        self._mem: OrderedDict[str, ScheduleExecutable] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.puts = 0

    def get_or_compile(self, schedule: PallasSchedule,
                       inputs: Optional[dict] = None, *, seed: int = 0
                       ) -> tuple[ScheduleExecutable, str, bool]:
        """-> ``(executable, key, hit)``."""
        key = schedule_key(schedule, seed=seed,
                           fingerprint=self.fingerprint)
        exe = self._mem.get(key)
        if exe is not None:
            self._mem.move_to_end(key)
            self.hits += 1
            return exe, key, True
        self.misses += 1
        exe = compile_schedule(schedule, inputs, seed=seed, key=key)
        self._mem[key] = exe
        self.puts += 1
        while len(self._mem) > self.capacity:
            self._mem.popitem(last=False)
            self.evictions += 1
        return exe, key, False

    def stats(self) -> dict:
        total = self.hits + self.misses
        return {"entries": len(self._mem), "capacity": self.capacity,
                "hits": self.hits, "misses": self.misses,
                "hit_rate": self.hits / total if total else 0.0,
                "evictions": self.evictions, "puts": self.puts,
                "fingerprint": self.fingerprint}
