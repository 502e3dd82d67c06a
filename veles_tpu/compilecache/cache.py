"""get_or_compile: the jit -> lower -> compile wrap with persistence.

:class:`CompileCache` sits between a ``jax.jit`` function and XLA: the
lowering is fingerprinted (:mod:`.keys`), looked up in the on-disk
store (:mod:`.store`), and either **deserialized** back into a loaded
executable (``jax.experimental.serialize_executable`` — milliseconds)
or **compiled** fresh and persisted for the next process.  Every
outcome is observable: ``veles_compile_cache_{hits,misses,bytes,
seconds_saved}_total`` in the process-global MetricsRegistry and
the span ``veles.compile.cache_load`` with ``cache="veles"`` for a load
off the store (``events.timed``: in the ring, the event file and a
running profile).  A fresh compile is filed by JAX's own events, as
``veles.compile.xla`` or, where JAX's persistent cache served the
module, ``veles.compile.cache_load`` with ``cache="jax"``
(``observability/compiles.py``).

Failure policy — the cache may only ever cost a recompile, never a
crash or a wrong result: a truncated/undeserializable entry is
quarantined (renamed aside) and the caller falls back to a fresh
compile; a full disk loses the *persist*, not the compile; any
environment drift (jax/jaxlib version, platform, device kind) changes
the key and misses cleanly.

:class:`AotStep` is the training-side adapter: a first-call AOT wrapper
around a jitted step function that lowers against the concrete call's
shapes, runs ``get_or_compile``, and executes the loaded executable
thereafter.  A bad cache ENTRY costs a recompile (above); a step that
fails to lower, compile or run raises — there is no second path that
could hide a program the device refused.
"""

import logging
import os
import pickle
import time

from ..config import root
from ..logger import events
from ..observability.registry import REGISTRY
from .keys import cache_key
from .manifest import WarmupManifest
from .store import ExecutableStore

log = logging.getLogger("veles_tpu.compilecache")

#: env var a supervisor (ElasticRunner) uses to hand the cache dir to
#: respawned children that don't re-read its programmatic config
CACHE_DIR_ENV = "VELES_COMPILE_CACHE_DIR"
MAX_BYTES_ENV = "VELES_COMPILE_CACHE_MAX_BYTES"

#: store blob format version — bump on layout change (old entries then
#: quarantine-and-recompile once, which is the upgrade path).
#: 2: entries name the devices their executable runs on
_FORMAT = 2


class CompileCache:
    """Persistent executable cache over one directory."""

    def __init__(self, directory, max_bytes=None, registry=None):
        registry = registry or REGISTRY
        self.store = ExecutableStore(directory, max_bytes=max_bytes)
        self.manifest = WarmupManifest(
            os.path.join(self.store.directory, "warmup_manifest.json"))
        self._c_hits = registry.counter(
            "veles_compile_cache_hits_total",
            "Executable cache hits (deserialize instead of compile)")
        self._c_misses = registry.counter(
            "veles_compile_cache_misses_total",
            "Executable cache misses (fresh XLA compile)")
        self._c_bytes = registry.counter(
            "veles_compile_cache_bytes_total",
            "Bytes read from + written to the executable store")
        self._c_saved = registry.counter(
            "veles_compile_cache_seconds_saved_total",
            "Recorded compile seconds avoided by cache hits, net of "
            "deserialization time")
        self._quarantined = set()   # keys warned about (log once)

    # -- the core ------------------------------------------------------------
    def get_or_compile(self, jitted, *arg_structs, name="jit",
                       key_extra=None):
        """Lower ``jitted`` at ``arg_structs`` and return
        ``(loaded_or_compiled, cache_hit)``.

        ``cache_hit`` is True when the executable came off disk, False
        when XLA compiled it fresh (and the entry was persisted).
        """
        lowered = jitted.lower(*arg_structs)
        return self.load_or_compile(lowered, name=name,
                                    key_extra=key_extra)

    def load_or_compile(self, lowered, name="jit", key_extra=None):
        """Same contract as :meth:`get_or_compile`, from a Lowered."""
        key = cache_key(lowered, extra=key_extra)
        loaded = self._try_load(key, name)
        if loaded is not None:
            return loaded, True
        t0 = time.perf_counter()
        compiled = lowered.compile()
        self._c_misses.inc()
        self._persist(key, compiled, time.perf_counter() - t0, name)
        return compiled, False

    def _try_load(self, key, name):
        blob = self.store.get(key)
        if blob is None:
            return None
        with events.timed("compile.cache_load", cache="veles",
                          module=str(name), key=key[:16],
                          bytes=len(blob)) as span:
            try:
                entry = pickle.loads(blob)
                if entry["format"] != _FORMAT or entry["key"] != key:
                    raise ValueError("entry format/key mismatch")
                import jax
                from jax.experimental import serialize_executable
                # load onto the devices the executable was compiled for:
                # left to its default, deserialize_and_load spreads a
                # one-device program over EVERY local device and its first
                # call then fails ("expected N shards")
                by_id = {d.id: d for d in jax.devices()}
                loaded = serialize_executable.deserialize_and_load(
                    *entry["exe"],
                    execution_devices=[by_id[i] for i in entry["devices"]])
            except Exception as exc:  # noqa: BLE001 — ANY bad entry: miss
                span.count(corrupt=1)   # a hit that ended in a recompile
                self.store.quarantine(key, reason=str(exc)[:120])
                if key not in self._quarantined:
                    self._quarantined.add(key)
                    log.warning("compile cache: entry %s for %r was "
                                "corrupt (%s: %s); recompiling", key[:16],
                                name, type(exc).__name__, str(exc)[:200])
                return None
        dt = span.seconds
        self._c_hits.inc()
        self._c_bytes.inc(len(blob))
        self._c_saved.inc(max(0.0,
                              float(entry.get("compile_seconds", 0.0))
                              - dt))
        return loaded

    def _persist(self, key, compiled, compile_seconds, name):
        try:
            from jax.experimental import serialize_executable
            exe = serialize_executable.serialize(compiled)
            devices = [d.id for d in
                       compiled.runtime_executable().local_devices()]
            blob = pickle.dumps({"format": _FORMAT, "key": key,
                                 "name": str(name), "devices": devices,
                                 "compile_seconds":
                                     round(float(compile_seconds), 4),
                                 "exe": exe},
                                protocol=pickle.HIGHEST_PROTOCOL)
        except Exception as exc:  # noqa: BLE001 — unserializable
            # executable (backend without serialization support): the
            # compile still succeeded, this process just stays warm-only
            log.info("compile cache: executable for %r not serializable "
                     "(%s: %s); not persisted", name,
                     type(exc).__name__, str(exc)[:200])
            return
        self._c_bytes.inc(self.store.put(key, blob))

    # -- stats ---------------------------------------------------------------
    def stats(self):
        return {"directory": self.store.directory,
                "entries": len(self.store.entries()),
                "total_bytes": self.store.total_bytes(),
                "max_bytes": self.store.max_bytes,
                "hits": int(self._c_hits.value),
                "misses": int(self._c_misses.value)}


# -- config resolution --------------------------------------------------------

def resolve_config():
    """(directory_or_None, max_bytes) from
    ``root.common.compile_cache.{enabled, dir, max_bytes}`` with the
    :data:`CACHE_DIR_ENV` / :data:`MAX_BYTES_ENV` env fallbacks.  A
    None directory means the cache is OFF — exact pre-cache behavior."""
    cfg = root.common.compile_cache
    if not cfg.get("enabled", True):
        return None, None
    directory = cfg.get("dir", None) or os.environ.get(CACHE_DIR_ENV)
    max_bytes = cfg.get("max_bytes", None)
    if max_bytes is None and os.environ.get(MAX_BYTES_ENV):
        try:
            max_bytes = int(os.environ[MAX_BYTES_ENV])
        except ValueError:
            max_bytes = None
    return (str(directory) if directory else None), max_bytes


_instances = {}


def default_cache():
    """The process-wide :class:`CompileCache` for the configured dir,
    or None when no dir is configured (cache off)."""
    directory, max_bytes = resolve_config()
    if not directory:
        return None
    key = (os.path.abspath(directory), max_bytes)
    cache = _instances.get(key)
    if cache is None:
        cache = _instances[key] = CompileCache(directory,
                                               max_bytes=max_bytes)
    return cache


def reset_default_caches():
    """Drop memoized instances (tests that switch config dirs)."""
    _instances.clear()


def inject_env(env=None):
    """Return ``env`` (default: a copy of os.environ) with the
    configured cache dir exported for a child process — how
    ElasticRunner respawns inherit the cache without re-reading the
    supervisor's programmatic config.  Also forwards the engine-level
    JAX persistent compilation cache dir when set."""
    directory, max_bytes = resolve_config()
    jax_cc = root.common.engine.get("compilation_cache_dir", None)
    # the tuning store rides the same respawn plumbing: children
    # resolve the SAME winners, so a respawn recompiles nothing new
    # (literal env name — importing veles_tpu.autotune here would cycle)
    tune_dir = root.common.get("autotune", {}).get("dir", None)
    if not directory and not jax_cc and not tune_dir:
        return env
    env = dict(os.environ if env is None else env)
    if directory:
        env.setdefault(CACHE_DIR_ENV, os.path.abspath(directory))
        if max_bytes:
            env.setdefault(MAX_BYTES_ENV, str(int(max_bytes)))
    if jax_cc:
        # jax config options read their env default at import time in
        # the child — the one-knob satellite rides along
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       os.path.abspath(str(jax_cc)))
    if tune_dir:
        env.setdefault("VELES_AUTOTUNE_DIR",
                       os.path.abspath(str(tune_dir)))
    return env


# -- the training-side adapter ------------------------------------------------


def _placed_format(a):
    """``a.format`` where the array ``a`` lies on its device in another
    layout than the device's default for its shape, else None (host
    values, and every array nobody placed)."""
    fmt = getattr(a, "format", None)
    if fmt is None or fmt.layout is None:
        return None
    from jax.experimental.layout import Layout
    device = next(iter(a.devices()))
    default = Layout.from_pjrt_layout(device.client.get_default_layout(
        a.dtype, a.sharding.shard_shape(a.shape), device))
    return None if fmt.layout == default else fmt


class AotStep:
    """First-call AOT wrapper around a jitted step function.

    The fused train step's shapes are only known at the first call (the
    loader owns them), so the wrapper lowers THERE: arg shapes/dtypes
    become ``ShapeDtypeStruct``s (python int/float scalars pinned to
    int32/float32, matching what the jit trace would produce), the
    executable comes from :meth:`CompileCache.get_or_compile`, and
    every later call runs it directly.

    Lowering, compiling and running raise like the wrapped function
    would: only a bad cache entry is absorbed, inside
    :meth:`CompileCache.load_or_compile`, at the cost of a recompile.

    Interface parity with ``jax.jit`` functions where the codebase
    relies on it: ``__wrapped__`` (``FusedTrainStep._lower_gather_train``
    lowers the raw function again), ``lower`` (the compiler's cost model)
    and
    ``_cache_size`` (the StepProfiler's recompile accounting: the
    executables this callable holds, the jit's own, which the AOT path
    never uses, and the one compiled or loaded here).

    ``cache`` None (no directory configured) compiles without a store:
    for a step that has to be an AOT executable whatever the
    configuration (``FusedTrainStep._place_data``).
    """

    def __init__(self, jitted, cache, name, key_extra=None):
        self._jitted = jitted
        self._cache = cache
        self._name = name
        self._key_extra = key_extra
        self._compiled = None
        self.cache_hit = None       # None until the first call decides
        self.lower = jitted.lower
        wrapped = getattr(jitted, "__wrapped__", None)
        if wrapped is not None:
            self.__wrapped__ = wrapped

    def _cache_size(self):
        fn = getattr(self._jitted, "_cache_size", None)
        try:
            own = int(fn()) if callable(fn) else 0
        except Exception:  # noqa: BLE001 — diagnostics never raise
            own = 0
        return own + (self._compiled is not None)

    # scalar pinning: a python int/float traces as a weak 32-bit scalar
    # under the default x64-off config; the AOT struct pins the same
    # width strongly and the call-side twin converts to match
    @staticmethod
    def _leaf_struct(a):
        import jax
        import numpy
        if isinstance(a, (bool, numpy.bool_)):
            return jax.ShapeDtypeStruct((), numpy.bool_)
        if isinstance(a, (int, numpy.integer)):
            return jax.ShapeDtypeStruct((), numpy.int32)
        if isinstance(a, (float, numpy.floating)):
            return jax.ShapeDtypeStruct((), numpy.float32)
        # an array placed in another layout than its device's default
        # keeps it: the lowering then names it (``mhlo.layout_mode``), so
        # the key does too, and an executable cached for the default
        # layout is never handed this array
        return jax.ShapeDtypeStruct(numpy.shape(a), a.dtype,
                                    sharding=_placed_format(a))

    @staticmethod
    def _leaf_harden(a):
        import numpy
        if isinstance(a, (bool, numpy.bool_)):
            return numpy.bool_(a)
        if isinstance(a, (int, numpy.integer)):
            return numpy.int32(a)
        if isinstance(a, (float, numpy.floating)):
            return numpy.float32(a)
        return a

    def compile(self, lowered):
        """Compile ``lowered`` now and run it from here on; returns the
        executable.  For a caller that lowers the wrapped function itself
        (the gather train step leaves one argument's layout to the
        compiler); the first call does the same from its arguments."""
        if self._cache is None:
            self._compiled = lowered.compile()
        else:
            self._compiled, self.cache_hit = self._cache.load_or_compile(
                lowered, name=self._name, key_extra=self._key_extra)
        return self._compiled

    def _ensure_compiled(self, args):
        import jax
        structs = jax.tree_util.tree_map(self._leaf_struct, args)
        self.compile(self._jitted.lower(*structs))

    def __call__(self, *args):
        import jax
        if self._compiled is None:
            self._ensure_compiled(args)
        return self._compiled(
            *jax.tree_util.tree_map(self._leaf_harden, args))
