"""AOT-serialized device programs: kill the XLA cold start.

The worst number in the repo is time-to-first-audit: every restart used
to re-pay the XLA compilation of each template's sweep programs (~10-120s
at audit scale) that the reference OPA interpreter line never pays. The
persistent XLA compilation cache (ir/driver.enable_compile_cache) already
removes the *compiler* time on a warm machine, but still re-traces, re-
lowers, and round-trips every program through the cache on each boot.

This module closes the rest of the gap:

  * ``AotStore`` — an on-disk store of *serialized compiled executables*
    (jax.experimental.serialize_executable), keyed by (program
    fingerprint, jit tag + static config, argument shape signature,
    backend/topology, jax version). A warm boot deserializes the exact
    device program in ~0.1s instead of recompiling it. The store also
    persists the driver's *warm sweep signatures* per program
    fingerprint, so a restarted process knows — before the first sweep —
    which shapes are deserialize-and-go and dispatches them on the
    device immediately.
  * ``AotJit`` — a drop-in wrapper for ``jax.jit`` used by
    CompiledTemplate/JoinCompiled: per argument-shape signature it first
    tries the store (source="aot"), then lowers+compiles, classifying
    the compile as a persistent-XLA-cache hit (source="cache") or a
    cold compile (source="fresh") via jax's cache-hit monitoring events.
    Compiles are timed into the shared PhaseTimers ("compile" phase, so
    audit traces gain a compile stage) and reported through
    ``gatekeeper_tpu_compile_{seconds,total}{source,outcome}``.

Everything here is best-effort: a store that cannot serialize (backend
without executable serialization support, unwritable volume, version
skew) degrades to plain ``jax.jit`` + the persistent XLA cache — never
an error on the serving path. Entries are only trusted when the RESOLVED
program fingerprint matches (interned string ids are embedded in the
program constants, so a vocab mismatch changes the fingerprint and
safely misses).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
import time
from collections import deque
from typing import Any, Optional

log = logging.getLogger("gatekeeper_tpu.ir.aot")


class WouldCompile(Exception):
    """Raised by AotJit instead of compiling while a no_inline_compile()
    scope is active: the caller promised this dispatch would be
    deserialize-and-go (a warm-boot-adopted sweep signature), so a store
    miss must bounce back to the host-fallback/background-warm path
    rather than stall the serving thread on XLA."""


_guard = threading.local()


class no_inline_compile:
    """Context manager: within the scope, an AotJit that cannot answer
    from its in-memory/on-disk executables raises WouldCompile instead
    of lowering+compiling inline. Thread-local (background warm threads
    keep compiling freely)."""

    def __enter__(self):
        self._prev = getattr(_guard, "active", False)
        _guard.active = True
        return self

    def __exit__(self, *exc):
        _guard.active = self._prev
        return False

# global fresh/cache/aot counters, readable by tests and bench runs that
# span several drivers in one process (the per-store stats reset with
# the store object)
COMPILE_COUNTS = {"aot": 0, "cache": 0, "fresh": 0, "error": 0}
_counts_lock = threading.Lock()

# persistent XLA cache events: lookups ("requests"), answers ("hits"),
# and compiles written back ("misses": jax reports a miss on write,
# so compiles under the min-compile-time floor count in neither)
_cache_events = {"requests": 0, "hits": 0, "misses": 0}
_monitor_registered = False


def _register_monitor() -> None:
    """Count jax persistent-compilation-cache hits via the monitoring
    events jax emits around every backend compile; AotJit diffs the hit
    counter to label a compile "cache" vs "fresh"."""
    global _monitor_registered
    if _monitor_registered:
        return
    _monitor_registered = True
    from jax._src import monitoring

    def cb(event, **kw):
        if event.endswith("/compile_requests_use_cache"):
            _cache_events["requests"] += 1
        elif event.endswith("/cache_hits"):
            _cache_events["hits"] += 1
        elif event.endswith("/cache_misses"):
            _cache_events["misses"] += 1

    monitoring.register_event_listener(cb)


def xla_cache_hits() -> int:
    return _cache_events["hits"]


def xla_cache_events() -> dict:
    """Persistent XLA cache {"requests", "hits", "misses"} events seen
    by this process (see _cache_events)."""
    return dict(_cache_events)


def _report_compile(source: str, outcome: str, seconds: float) -> None:
    with _counts_lock:
        COMPILE_COUNTS[source if outcome == "ok" else "error"] = \
            COMPILE_COUNTS.get(source if outcome == "ok" else "error",
                               0) + 1
    try:
        from ..control.metrics import report_compile

        report_compile(source, outcome, seconds)
    except Exception:  # metrics backend optional in embedders
        pass


def arg_sig(args: tuple) -> tuple:
    """Canonical, hashable, cross-process-stable shape signature of a
    jit call's arguments: the flattened leaves' (shape, dtype) plus the
    treedef structure. Two processes computing the same signature get
    byte-identical keys (dict orders are insertion-deterministic in the
    extraction/encoding pipelines)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = tuple(
        (tuple(int(d) for d in getattr(a, "shape", ())),
         str(getattr(a, "dtype", type(a).__name__)))
        for a in leaves)
    return (sig, str(treedef))


def _jsonable(x):
    if isinstance(x, tuple):
        return [_jsonable(v) for v in x]
    return x


def _detuple(x):
    if isinstance(x, list):
        return tuple(_detuple(v) for v in x)
    return x


def program_fingerprint(program: Any, kind: str = "") -> str:
    """Fingerprint of a RESOLVED Program (resolve_consts already ran):
    interned string/row/number ids are embedded in the constants, so two
    processes only share a fingerprint when their vocab assignments for
    the program's constants match — exactly the condition under which a
    serialized executable is reusable."""
    body = repr(program).encode()
    return hashlib.sha256(kind.encode() + b"\x00" + body).hexdigest()


class AotStore:
    """Disk store of serialized executables + warm sweep signatures.

    Layout (under ``set_dir``'s path, itself normally
    ``<state-dir>/aot``):

        <dir>/<platform>-d<ndev>-jax<version>/
            manifest.jsonl          append-only: program entries + sigs
            <key>.aotx              pickled (payload, in_tree, out_tree)

    The platform subdir keys the whole store by backend + device count +
    jax version: executables never deserialize across any of those."""

    MANIFEST = "manifest.jsonl"
    # per-fingerprint warm-sig cap: sigs are tiny, but a churn-heavy
    # deployment must not grow them forever (oldest dropped first)
    MAX_SIGS_PER_FP = 256

    def __init__(self, path: Optional[str] = None):
        import os as _os

        self.dir: Optional[str] = None
        self._lock = threading.Lock()
        # fingerprint -> insertion-ordered {sig: None} (dict-as-set)
        self._sigs: dict[str, dict] = {}
        # fingerprint -> list of {"tag","static","asig","file"}
        self._entries: dict[str, list] = {}
        self._known_files: set = set()
        # global FIFO of (fingerprint, file) for bounded eviction:
        # template edits change the fingerprint, so without a cap stale
        # programs' .aotx blobs would accumulate on the state volume
        # forever; oldest-first eviction retires them
        self._order: list = []
        self.max_programs = int(_os.environ.get(
            "GATEKEEPER_TPU_AOT_MAX_PROGRAMS", "512"))
        self.stats = {"aot": 0, "cache": 0, "fresh": 0, "error": 0,
                      "aot_seconds": 0.0, "compile_seconds": 0.0}
        # per-kind recent compile events for /debug/templates
        self._events: dict[str, deque] = {}
        # tags whose executables this backend cannot serialize (e.g.
        # SPMD mesh programs on some runtimes): per-tag, so one broken
        # program class never disables the store for the rest
        self._serialize_broken: set = set()
        # prepack mode (the warm-cache CLI): when a compile answered by
        # the persistent XLA cache yields an unserializable executable,
        # recompile with the cache disabled to mint a durable entry —
        # worth full compile time offline, never on the serving path
        self.force_durable = False
        if path:
            self.set_dir(path)

    # ------------------------------------------------------------ config

    @property
    def enabled(self) -> bool:
        return self.dir is not None

    def _platform_key(self) -> str:
        import jax

        return (f"{jax.default_backend()}-d{len(jax.devices())}"
                f"-jax{jax.__version__}")

    def set_dir(self, path: str) -> bool:
        """Point the store at a directory (idempotent); loads the
        manifest. Returns False (store stays disabled) when the
        directory is unusable — a read-only volume must degrade to the
        plain jit path, not break serving."""
        _register_monitor()
        try:
            full = os.path.join(path, self._platform_key())
            os.makedirs(full, exist_ok=True)
            # probe writability once: os.makedirs succeeds on an
            # existing dir of a read-only volume
            probe = os.path.join(full, f".probe.{os.getpid()}")
            with open(probe, "w") as f:
                f.write("")
            os.unlink(probe)
        except OSError as e:
            log.warning("AOT program cache disabled (dir unusable): "
                        "%s: %s", path, e)
            return False
        with self._lock:
            self.dir = full
            self._load_manifest()
        log.info("AOT program cache at %s: %d serialized programs, "
                 "%d warm sweep signatures",
                 full, sum(len(v) for v in self._entries.values()),
                 sum(len(v) for v in self._sigs.values()))
        try:
            from ..control.metrics import report_aot_store

            report_aot_store(True, self.programs_count())
        except Exception:  # metrics backend optional in embedders
            pass
        return True

    def programs_count(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._entries.values())

    # ---------------------------------------------------------- manifest

    def _load_manifest(self) -> None:
        self._sigs.clear()
        self._entries.clear()
        self._known_files.clear()
        self._order.clear()
        path = os.path.join(self.dir, self.MANIFEST)
        try:
            with open(path) as f:
                lines = f.readlines()
        except OSError:
            return
        dropped = 0
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
                fp = rec["fp"]
                if rec.get("t") == "sig":
                    self._sigs.setdefault(
                        fp, {})[_detuple(rec["sig"])] = None
                elif rec.get("t") == "entry":
                    fn = rec["file"]
                    if fn in self._known_files:
                        continue
                    # an entry whose blob vanished (evicted by another
                    # process, manual cleanup) is dead weight
                    if not os.path.exists(os.path.join(self.dir, fn)):
                        dropped += 1
                        continue
                    self._known_files.add(fn)
                    self._entries.setdefault(fp, []).append({
                        "tag": rec["tag"],
                        "static": _detuple(rec["static"]),
                        "asig": _detuple(rec["asig"]),
                        "file": fn,
                    })
                    self._order.append((fp, fn))
            except Exception:
                continue  # torn tail line of a crashed writer
        self._evict_over_cap()
        live = len(self._order) + sum(len(v) for v in self._sigs.values())
        # the manifest is append-only between boots: compact it when
        # dead lines (duplicate sigs, evicted/vanished entries) dominate
        if dropped or len(lines) > 2 * live + 64:
            self._compact()

    def _evict_over_cap(self) -> None:
        """Retire oldest serialized programs beyond max_programs (FIFO:
        stale fingerprints from template edits age out first). Caller
        holds the lock (or is single-threaded in set_dir)."""
        evicted = False
        while self.max_programs > 0 and len(self._order) > \
                self.max_programs:
            fp, fn = self._order.pop(0)
            self._known_files.discard(fn)
            ents = self._entries.get(fp, [])
            self._entries[fp] = [e for e in ents if e["file"] != fn]
            if not self._entries[fp]:
                self._entries.pop(fp, None)
                self._sigs.pop(fp, None)
            try:
                os.unlink(os.path.join(self.dir, fn))
            except OSError:
                pass
            evicted = True
        if evicted:
            self._compact()

    def _compact(self) -> None:
        """Rewrite the manifest from in-memory state (atomic): drops
        evicted/vanished entries and duplicate sig lines so the
        append-only file can't grow without bound across boots."""
        path = os.path.join(self.dir, self.MANIFEST)
        tmp = path + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                for fp, sigs in self._sigs.items():
                    for sig in sigs:
                        f.write(json.dumps(
                            {"t": "sig", "fp": fp,
                             "sig": _jsonable(sig)},
                            separators=(",", ":")) + "\n")
                for fp, fn in self._order:
                    ent = next((e for e in self._entries.get(fp, ())
                                if e["file"] == fn), None)
                    if ent is None:
                        continue
                    f.write(json.dumps(
                        {"t": "entry", "fp": fp, "tag": ent["tag"],
                         "static": _jsonable(ent["static"]),
                         "asig": _jsonable(ent["asig"]),
                         "file": fn}, separators=(",", ":")) + "\n")
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError as e:
            log.warning("AOT manifest compaction failed: %s", e)
            try:
                os.unlink(tmp)
            except OSError:
                pass

    def _append_manifest(self, rec: dict) -> None:
        try:
            with open(os.path.join(self.dir, self.MANIFEST), "a") as f:
                f.write(json.dumps(rec, separators=(",", ":")) + "\n")
                f.flush()
        except OSError as e:
            log.warning("AOT manifest append failed: %s", e)

    # ------------------------------------------------------- sweep sigs

    def record_sig(self, fingerprint: str, sig: tuple) -> None:
        """Persist one warm driver sweep signature: a later boot marks
        this (fingerprint, shape) warm before its first sweep and
        dispatches on the device (deserialize-and-go) immediately."""
        if not self.enabled:
            return
        with self._lock:
            have = self._sigs.setdefault(fingerprint, {})
            if sig in have:
                return
            have[sig] = None
            while len(have) > self.MAX_SIGS_PER_FP:
                have.pop(next(iter(have)))
            self._append_manifest(
                {"t": "sig", "fp": fingerprint, "sig": _jsonable(sig)})

    def sigs_for(self, fingerprint: str) -> set:
        with self._lock:
            return set(self._sigs.get(fingerprint, ()))

    def entries_for(self, fingerprint: str) -> list:
        with self._lock:
            return list(self._entries.get(fingerprint, ()))

    # ------------------------------------------------------ executables

    def entry_key(self, fingerprint: str, tag: str, static: tuple,
                  asig: tuple) -> str:
        h = hashlib.sha256(repr((fingerprint, tag, static,
                                 asig)).encode()).hexdigest()
        return h[:40]

    def load(self, key: str):
        """Deserialize one stored executable, or None. Any failure
        (missing, corrupt, version-skewed pickle) is a miss."""
        if not self.enabled:
            return None
        path = os.path.join(self.dir, key + ".aotx")
        try:
            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.loads(f.read())
        except FileNotFoundError:
            return None
        except Exception as e:
            log.warning("AOT entry %s unreadable (recompiling): %s: %s",
                        key, type(e).__name__, e)
            return None
        try:
            from jax.experimental import serialize_executable as se

            return se.deserialize_and_load(payload, in_tree, out_tree)
        except Exception as e:
            log.warning("AOT entry %s failed to deserialize "
                        "(recompiling): %s: %s", key,
                        type(e).__name__, e)
            return None

    def save(self, key: str, compiled, fingerprint: str, tag: str,
             static: tuple, asig: tuple) -> bool:
        """Serialize + persist one compiled executable (atomic write).
        A program class (tag) that cannot serialize on this backend is
        marked broken after the first failure and skipped from then on
        (the persistent XLA cache remains the fallback for it)."""
        if not self.enabled or tag in self._serialize_broken:
            return False
        try:
            from jax.experimental import serialize_executable as se

            payload = se.serialize(compiled)
        except Exception as e:
            self._serialize_broken.add(tag)
            log.warning("executable serialization unsupported for %r "
                        "programs here (falling back to the persistent "
                        "XLA cache for them): %s: %s", tag,
                        type(e).__name__, e)
            return False
        try:
            # round-trip probe BEFORE persisting: an executable that XLA
            # itself loaded from its persistent compilation cache can
            # serialize to a payload missing its kernel symbols (observed
            # on the CPU thunk runtime: deserialize dies with "Symbols
            # not found"). A corrupt entry would poison every warm boot,
            # so only entries proven to deserialize are stored; the
            # persistent XLA cache remains the fallback for the rest.
            se.deserialize_and_load(*payload)
        except Exception as e:
            log.debug("AOT entry for %s/%s not persisted (payload fails "
                      "round-trip; the persistent XLA cache still covers "
                      "this program): %s: %s", fingerprint[:12],
                      tag, type(e).__name__, e)
            return False
        blob = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
        path = os.path.join(self.dir, key + ".aotx")
        tmp = path + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError as e:
            log.warning("AOT entry write failed: %s", e)
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        with self._lock:
            if key + ".aotx" not in self._known_files:
                self._known_files.add(key + ".aotx")
                self._entries.setdefault(fingerprint, []).append({
                    "tag": tag, "static": static, "asig": asig,
                    "file": key + ".aotx"})
                self._order.append((fingerprint, key + ".aotx"))
                self._append_manifest({
                    "t": "entry", "fp": fingerprint, "tag": tag,
                    "static": _jsonable(static),
                    "asig": _jsonable(asig), "file": key + ".aotx"})
                self._evict_over_cap()
        return True

    # ---------------------------------------------------- observability

    def note(self, source: str, seconds: float, kind: str = "",
             tag: str = "", key: tuple = (),
             outcome: str = "ok") -> None:
        with self._lock:
            if outcome == "ok":
                self.stats[source] = self.stats.get(source, 0) + 1
                sec_key = ("aot_seconds" if source == "aot"
                           else "compile_seconds")
                self.stats[sec_key] = self.stats.get(sec_key, 0.0) \
                    + seconds
            else:
                self.stats["error"] = self.stats.get("error", 0) + 1
            ev = self._events.setdefault(kind or "?", deque(maxlen=8))
            ev.append({"tag": tag, "source": source,
                       "seconds": round(seconds, 3),
                       "outcome": outcome,
                       "bucket_key": repr(key)})
        _report_compile(source, outcome, seconds)

    def events_for(self, kind: str) -> list:
        with self._lock:
            return list(self._events.get(kind, ()))

    def stats_snapshot(self) -> dict:
        with self._lock:
            out = dict(self.stats)
        out["enabled"] = self.enabled
        out["dir"] = self.dir
        return out


class AotJit:
    """``jax.jit`` with a persistent executable cache behind it.

    Call semantics are identical to the wrapped jit. Per argument-shape
    signature, the call resolves (once) to a compiled executable:
    store hit -> deserialize ("aot"); miss -> lower+compile ("cache"
    when the persistent XLA cache answered, else "fresh") and persist.
    Executable-vs-argument mismatches (layout/committed-device skew)
    fall back to the plain jit permanently for that signature — the
    wrapper must never fail a call the jit would have served."""

    def __init__(self, fn, store: Optional[AotStore] = None,
                 fingerprint: str = "", tag: str = "",
                 static: tuple = (), kind: str = ""):
        import jax

        self._jit = jax.jit(fn)
        self._store = store
        self._fingerprint = fingerprint
        self._tag = tag
        self._static = tuple(static)
        self._kind = kind
        self._compiled: dict = {}
        self._lock = threading.Lock()

    # jax.jit API surface used elsewhere (profiling.compiled_hlo)
    def lower(self, *args, **kw):
        return self._jit.lower(*args, **kw)

    def ready(self, asig: tuple) -> bool:
        return asig in self._compiled

    def preload(self, asig: tuple, key: str) -> bool:
        """Deserialize a manifest entry into the in-memory cache without
        needing live arguments (ingest-time background prewarm)."""
        store = self._store
        if store is None or not store.enabled:
            return False
        with self._lock:
            if asig in self._compiled:
                return True
        t0 = time.monotonic()
        comp = store.load(key)
        if comp is None:
            return False
        with self._lock:
            self._compiled.setdefault(asig, comp)
        store.note("aot", time.monotonic() - t0, kind=self._kind,
                   tag=self._tag, key=self._static + (asig,))
        return True

    def __call__(self, *args):
        store = self._store
        if store is None or not store.enabled:
            # no store -> no warm-boot adoption is possible, so a
            # no_inline_compile scope can't be violated here
            return self._jit(*args)
        asig = arg_sig(args)
        ent = self._compiled.get(asig)
        if ent is None:
            ent = self._acquire(asig, args)
        if ent is self._jit:
            return ent(*args)
        try:
            return ent(*args)
        except Exception as e:
            # layout/type skew between the stored executable and the
            # live arguments: serve from the jit and stop consulting
            # the entry for this signature
            log.warning("AOT executable rejected live args for %s/%s "
                        "(falling back to jit): %s: %s", self._kind,
                        self._tag, type(e).__name__, e)
            with self._lock:
                self._compiled[asig] = self._jit
            return self._jit(*args)

    def _acquire(self, asig: tuple, args: tuple):
        from ..utils import profiling

        store = self._store
        key = store.entry_key(self._fingerprint, self._tag,
                              self._static, asig)
        t0 = time.monotonic()
        comp = store.load(key)
        if comp is not None:
            store.note("aot", time.monotonic() - t0, kind=self._kind,
                       tag=self._tag, key=self._static + (asig,))
            with self._lock:
                self._compiled.setdefault(asig, comp)
            return self._compiled[asig]
        if getattr(_guard, "active", False):
            # a no_inline_compile scope promised deserialize-and-go
            # (warm-boot-adopted signature) but the store can't answer:
            # bounce to the caller's host-fallback path, never stall
            # the serving thread on XLA
            raise WouldCompile(self._kind, self._tag)
        hits0 = xla_cache_hits()
        t0 = time.monotonic()
        try:
            with profiling.timers().phase("compile"):
                comp = self._jit.lower(*args).compile()
        except Exception as e:
            store.note("fresh", time.monotonic() - t0, kind=self._kind,
                       tag=self._tag, key=self._static + (asig,),
                       outcome="error")
            raise e
        dt = time.monotonic() - t0
        source = "cache" if xla_cache_hits() > hits0 else "fresh"
        store.note(source, dt, kind=self._kind, tag=self._tag,
                   key=self._static + (asig,))
        saved = store.save(key, comp, self._fingerprint, self._tag,
                           self._static, asig)
        if not saved and store.force_durable and source == "cache" \
                and self._tag not in store._serialize_broken:
            comp = self._mint_durable(store, key, asig, args) or comp
        with self._lock:
            self._compiled.setdefault(asig, comp)
        return self._compiled[asig]

    def _mint_durable(self, store: AotStore, key: str, asig: tuple,
                      args: tuple):
        """Prepack-only (store.force_durable): a compile the persistent
        XLA cache answered can serialize to a corrupt payload (save's
        round-trip probe refused it), so recompile with the cache
        disabled — a genuinely fresh executable round-trips — and
        persist that. Full compile time, paid offline by the warm-cache
        CLI so serving boots never have to."""
        import jax

        t0 = time.monotonic()
        try:
            # two process-wide caches would silently hand the same
            # unserializable executable back: jax memoizes (a) its
            # is-the-cache-usable decision the first time any compile
            # runs (so flipping the config alone is a no-op) and (b)
            # the compiled executable itself per (module, options) in
            # pxla's compilation LRU. Reset both around the flip —
            # offline-only cost, this path never runs while serving.
            from jax._src import compilation_cache as _cc
            from jax._src.interpreters import pxla as _pxla

            prev = jax.config.jax_enable_compilation_cache
            jax.config.update("jax_enable_compilation_cache", False)
            _cc.reset_cache()
            _pxla._cached_compilation.cache_clear()
            try:
                comp = self._jit.lower(*args).compile()
            finally:
                jax.config.update("jax_enable_compilation_cache", prev)
                _cc.reset_cache()
        except Exception as e:
            log.warning("durable recompile for %s/%s failed: %s: %s",
                        self._kind, self._tag, type(e).__name__, e)
            return None
        store.note("fresh", time.monotonic() - t0, kind=self._kind,
                   tag=self._tag, key=self._static + (asig,))
        store.save(key, comp, self._fingerprint, self._tag,
                   self._static, asig)
        return comp
