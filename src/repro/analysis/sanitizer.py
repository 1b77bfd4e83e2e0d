"""Runtime sanitizers: the dynamic half of the contract linter.

The static rules (RL001-RL006) prove properties of the source; these
context managers watch the same contracts at execution time, so the
two cross-validate:

* :func:`transfer_sanitizer` — arms
  ``jax.transfer_guard_device_to_host("disallow")``: implicit
  device->host transfers raise, while the blessed *explicit*
  ``jax.device_get`` fetch points keep working (exactly the RL003
  split). Caveat: on a CPU-only backend host and device share buffers,
  so ``np.asarray(jax_array)`` is zero-copy and the guard cannot trip —
  there the teeth are the HOST_TRANSFER_COUNT pin and the ledgers
  below; on accelerator backends the guard bites for real.

* :class:`CompileWatcher` — ``jax.log_compiles``-based recompile
  detector: captures every XLA "Compiling <name>" event while active,
  so a test can assert a sweep triggered no recompilation beyond its
  declared multiplicity (compile_sites.toml).

* :class:`TraceLedger` — hooks ``simulator.TRACE_HOOK`` to record the
  static ``site`` hull of every sweep-step trace.
  :meth:`SanitizerSession.assert_one_trace_per_bucket` turns that into
  the planner pipeline's contract: under ``pipeline=True`` each plan
  bucket compiles exactly once, and a violation fails with the
  offending bucket's hull tag (not just a drifted total).

Wired into pytest via the ``sweep_sanitizer`` fixture in
tests/conftest.py and exercised by tests/test_sanitizer.py (the CI
lint-canary leg).
"""
from __future__ import annotations

import contextlib
import logging
import re
from collections import Counter
from dataclasses import dataclass

import jax

from repro.core import simulator
from repro.core.topology import full_site_tag

#: "Compiling <name> with global shapes ..."; JAX 0.9 writes the name
#: as ``jit(<name>)``, older releases bare — both yield <name>
_COMPILE_RE = re.compile(
    r"Compiling (?:jit\((\S+?)\)|(\S+)) with global shapes")
#: the logger jax.log_compiles routes "Compiling <name> ..." through
_COMPILE_LOGGER = "jax._src.interpreters.pxla"


@contextlib.contextmanager
def transfer_sanitizer():
    """Disallow implicit device->host transfers; explicit device_get
    stays legal. Scoped to the device->host direction only: feeding
    numpy scenario tables INTO a jitted sweep is normal dispatch, the
    RL003 contract polices what silently comes back OUT."""
    with jax.transfer_guard_device_to_host("disallow"):
        yield


class CompileWatcher(logging.Handler):
    """Collects XLA compile events (function names) while active."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.events: list = []

    def emit(self, record):
        m = _COMPILE_RE.search(record.getMessage())
        if m:
            self.events.append(m.group(1) or m.group(2))

    def compiles_of(self, name: str) -> int:
        return sum(1 for e in self.events if e == name)

    def __enter__(self):
        self._cm = jax.log_compiles()
        self._cm.__enter__()
        logging.getLogger(_COMPILE_LOGGER).addHandler(self)
        return self

    def __exit__(self, *exc):
        logging.getLogger(_COMPILE_LOGGER).removeHandler(self)
        self._cm.__exit__(*exc)
        return False


class TraceLedger:
    """Records the static site hull of every sweep-step trace."""

    def __init__(self):
        self.sites: list = []
        self._count0 = 0
        self._prev = None

    @property
    def tags(self) -> list:
        return [full_site_tag(s) for s in self.sites]

    def new_traces(self) -> int:
        return simulator.TRACE_COUNT - self._count0

    def _record(self, site):
        self.sites.append(site)
        if self._prev is not None:
            self._prev(site)

    def __enter__(self):
        self._prev = simulator.TRACE_HOOK
        self._count0 = simulator.TRACE_COUNT
        simulator.TRACE_HOOK = self._record
        return self

    def __exit__(self, *exc):
        simulator.TRACE_HOOK = self._prev
        return False


@dataclass
class SanitizerSession:
    compiles: CompileWatcher
    traces: TraceLedger

    def assert_one_trace_per_bucket(self, plan):
        """The planner pipeline's per-bucket compile contract.

        Under ``pipeline=True`` every bucket of ``plan`` must have
        produced exactly one sweep-step trace — no bucket retraced
        (shape drift inside a bucket) and no trace for a hull the plan
        never declared. Failure names the offending hull tag so the
        guilty bucket is identifiable without bisecting.
        """
        counts = Counter(self.traces.tags)
        if hasattr(plan, "buckets"):          # planner.SweepPlan
            planned = [full_site_tag(b.hull) for b in plan.buckets]
        else:                                 # run_sweep_planned report
            planned = [b["hull"] for b in plan["buckets"]]
        for tag in planned:
            n = counts.get(tag, 0)
            if n > 1:
                raise AssertionError(
                    f"bucket hull {tag} was traced {n}x (expected "
                    "exactly 1): the pipeline retraced a bucket — "
                    "batch-shape or static-arg drift inside the "
                    "bucket")
            if n == 0:
                raise AssertionError(
                    f"bucket hull {tag} was never traced: the ledger "
                    "missed a bucket (stale _sweep_runner cache? "
                    "call simulator._sweep_runner.cache_clear() "
                    "before arming the ledger)")
        stray = set(counts) - set(planned)
        if stray:
            raise AssertionError(
                f"traces for undeclared hull(s) {sorted(stray)}: the "
                "pipeline compiled outside the plan's buckets")


@contextlib.contextmanager
def sweep_sanitizer():
    """transfer guard + compile watcher + trace ledger, as one session."""
    with transfer_sanitizer(), CompileWatcher() as cw, \
            TraceLedger() as tl:
        yield SanitizerSession(compiles=cw, traces=tl)
