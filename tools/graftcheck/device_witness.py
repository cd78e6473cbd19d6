"""Runtime device witness: compile-event + transfer instrumentation
(test-only, lockdep-style) — the dynamic side of ``devicecheck``.

The static analyzer claims two properties of the hot serving paths:
steady-state serving never re-enters XLA compilation, and every implicit
device->host transfer is confined to the named fetch stage or carries a
reviewed allowlist reason.  This witness checks both claims against what
actually happens, the same way ``witness.py`` checks the static lock
graph:

* **compile events** — one module-level ``jax.monitoring`` listener
  counts ``/jax/core/compile/backend_compile_duration`` events (fires on
  every backend compile INCLUDING recompiles and loads from the
  persistent cache; silent on in-memory executable-cache hits —
  verified against jax 0.9).  ``end_warmup()`` snapshots the
  count; any later compile is a post-warmup recompile and fails
  ``check()``.  ``jax.monitoring`` has no per-listener unregister, so
  ONE process-wide listener feeds a monotonic counter and witnesses read
  deltas.

* **transfers** — ``install()`` swaps a recording proxy over the ``np``
  binding in every imported ``tfidf_tpu*`` module (exactly how the
  lockdep witness proxies ``threading``): ``np.asarray`` / ``np.array``
  / ``np.ascontiguousarray`` on a ``jax.Array`` argument records a
  ``(module, function)`` site from the caller's frame before delegating.
  Every observed site must appear in the static explained set
  (:func:`devicecheck.explained_transfer_sites`: the fetch stage, the
  sanctioned bulk stages, plus allowlisted-with-reason sites) or
  ``check()`` fails — each side validating the other.  Functions that ``import numpy`` locally (the
  fetch stage does, by design) bypass the module-namespace proxy; the
  static pass still covers them, which is why the exemption lives there.

* **transfer guard** — best-effort backend instrumentation: install()
  also sets ``jax.transfer_guard`` policies (``log`` by default; knob
  ``GRAFTCHECK_DEVICE_GUARD=disallow`` hard-fails).  On the CPU backend
  d2h of a zero-copy buffer is invisible to the guard (verified), so the
  namespace proxy above is the authoritative CPU-side observation; on a
  real TPU backend the guard adds C++-level coverage the proxy can't.

Vacuous-pass floor: ``check(min_observations=N)`` fails a run that
observed fewer than N device transfers — an instrumented run that saw
nothing proves nothing (the lockdep ``min_multilock_edges`` contract).

Like the lockdep witness: overhead makes this test-only — gate on
``GRAFTCHECK_DEVICE=1`` (see ``tests/conftest.py`` and
``make device-witness``).
"""

from __future__ import annotations

import os
import sys

_PACKAGE = "tfidf_tpu"

# ---------------------------------------------------------------------------
# process-wide compile counter (jax.monitoring has no unregister: one
# listener, installed once, survives for the process lifetime)
# ---------------------------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_COMPILES = {"n": 0}
_LISTENER_INSTALLED = [False]


def _on_event(name: str, *_a, **_kw) -> None:
    if name == _COMPILE_EVENT:
        _COMPILES["n"] += 1


def ensure_compile_listener() -> None:
    if _LISTENER_INSTALLED[0]:
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_event)
    _LISTENER_INSTALLED[0] = True


def compile_count() -> int:
    """Backend compiles observed so far in this process (monotonic;
    meaningful only after :func:`ensure_compile_listener`)."""
    return _COMPILES["n"]


# ---------------------------------------------------------------------------
# numpy proxy
# ---------------------------------------------------------------------------

class _NumpyProxy:
    """Delegating stand-in for the ``np`` binding in one package module:
    records fetcher calls whose first argument is a device array, then
    delegates. Attribute access falls through to real numpy, so
    ``np.float32`` / ``np.zeros`` / ``isinstance(x, np.ndarray)`` are
    untouched."""

    _FETCHERS = ("asarray", "array", "ascontiguousarray")

    def __init__(self, witness: "DeviceWitness", modname: str,
                 real) -> None:
        self._w = witness
        self._mod = modname
        self._real = real

    def __getattr__(self, name: str):
        real_fn = getattr(self._real, name)
        if name not in self._FETCHERS:
            return real_fn
        w, mod = self._w, self._mod

        def wrapper(*args, **kwargs):
            if args and w._is_device_array(args[0]):
                w._record(mod, sys._getframe(1).f_code.co_name, name)
            return real_fn(*args, **kwargs)
        wrapper.__name__ = name
        return wrapper


class DeviceWitness:
    """One instrumented run: install -> (warmup) -> end_warmup ->
    exercise -> uninstall -> check."""

    def __init__(self, explained: set | None = None,
                 guard: str | None = None) -> None:
        # (module, function) pairs the static cone explains; None =
        # compute from the committed allowlist + the fetch-stage seam
        if explained is None:
            from tools.graftcheck.core import SourceTree
            from tools.graftcheck.devicecheck import \
                explained_transfer_sites
            root = os.path.dirname(os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))
            explained = explained_transfer_sites(SourceTree(root))
        self.explained = set(explained)
        self.guard = guard or os.environ.get(
            "GRAFTCHECK_DEVICE_GUARD", "log")
        # (module, function, op) -> count
        self.observed: dict[tuple[str, str, str], int] = {}
        self._saved: list[tuple[dict, object]] = []
        self._guard_cm = None
        self._installed = False
        self._warmup_compiles: int | None = None
        self._install_compiles = 0

    # -- recording --------------------------------------------------------

    @staticmethod
    def _is_device_array(x) -> bool:
        jax = sys.modules.get("jax")
        if jax is None:
            return False
        try:
            return isinstance(x, jax.Array) and not isinstance(
                x, jax.core.Tracer)
        except Exception:
            return isinstance(x, jax.Array)

    def _record(self, module: str, func: str, op: str) -> None:
        key = (module, func, op)
        self.observed[key] = self.observed.get(key, 0) + 1

    # -- lifecycle --------------------------------------------------------

    def install(self) -> "DeviceWitness":
        assert not self._installed
        import numpy as _real_np

        import jax

        ensure_compile_listener()
        self._install_compiles = compile_count()
        for name, mod in list(sys.modules.items()):
            if mod is None or not (name == _PACKAGE or
                                   name.startswith(_PACKAGE + ".")):
                continue
            binding = mod.__dict__.get("np")
            if binding is not _real_np:
                continue     # no module-level numpy (or already proxied)
            short = name[len(_PACKAGE) + 1:] if name != _PACKAGE else ""
            proxy = _NumpyProxy(self, short, _real_np)
            self._saved.append((mod.__dict__, binding))
            mod.__dict__["np"] = proxy
        self._guard_cm = jax.transfer_guard(self.guard)
        self._guard_cm.__enter__()
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        for mod_dict, binding in self._saved:
            mod_dict["np"] = binding
        self._saved = []
        if self._guard_cm is not None:
            try:
                self._guard_cm.__exit__(None, None, None)
            except Exception:
                pass
            self._guard_cm = None
        self._installed = False

    def end_warmup(self) -> None:
        """Compiles up to here are warmup; any later one is a
        post-warmup recompile."""
        self._warmup_compiles = compile_count()

    # -- verdict ----------------------------------------------------------

    def post_warmup_compiles(self) -> int:
        base = (self._warmup_compiles
                if self._warmup_compiles is not None
                else self._install_compiles)
        return compile_count() - base

    def unexplained(self) -> list[tuple[str, str, str]]:
        return sorted(k for k in self.observed
                      if (k[0], k[1]) not in self.explained)

    def report(self) -> str:
        lines = [f"device witness: {sum(self.observed.values())} "
                 f"device-array transfer(s) at "
                 f"{len(self.observed)} site(s), "
                 f"{compile_count() - self._install_compiles} "
                 f"compile(s) since install"]
        for (mod, fn, op), n in sorted(self.observed.items()):
            mark = ("" if (mod, fn) in self.explained
                    else "  <-- UNEXPLAINED")
            lines.append(f"  {mod}.{fn} [np.{op}] x{n}{mark}")
        return "\n".join(lines)

    def check(self, *, max_post_warmup_compiles: int | None = None,
              min_observations: int = 0) -> None:
        """Raise AssertionError on any unexplained transfer, on more
        than ``max_post_warmup_compiles`` compiles after
        :meth:`end_warmup` (pass None to skip — suite-wide runs compile
        per test by design), or on a vacuous run that observed fewer
        than ``min_observations`` transfers."""
        problems: list[str] = []
        bad = self.unexplained()
        if bad:
            problems.append(
                f"{len(bad)} transfer site(s) the static cone did not "
                f"explain (add the code to the fetch stage, fix the "
                f"sync, or pin the devicecheck finding with a reviewed "
                f"reason): " + ", ".join(
                    f"{m}.{f} [np.{o}]" for m, f, o in bad))
        if max_post_warmup_compiles is not None:
            n = self.post_warmup_compiles()
            if n > max_post_warmup_compiles:
                problems.append(
                    f"{n} post-warmup XLA compile(s) (limit "
                    f"{max_post_warmup_compiles}): a corpus-dependent "
                    f"value is reaching a traced shape or static arg "
                    f"after warmup")
        if sum(self.observed.values()) < min_observations:
            problems.append(
                f"vacuous run: {sum(self.observed.values())} observed "
                f"transfer(s) < floor {min_observations} — the "
                f"instrumented suites no longer exercise the device "
                f"paths this witness exists to watch")
        if problems:
            raise AssertionError(
                "device witness FAILED:\n- " + "\n- ".join(problems)
                + "\n" + self.report())
