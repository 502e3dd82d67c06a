"""The compile monitor (``veles_tpu/observability/compiles.py``): JAX's
compile phases filed as the program's spans, one for each outermost
trace, under the span open on the thread that compiled, with the
persistent cache that served a module.  All on the CPU."""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy
import pytest

from veles_tpu import backends
from veles_tpu.logger import events
from veles_tpu.observability import compiles

from test_spans import by_seq, compiled

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def filed_since(seen):
    """The compile spans filed after the span numbered ``seen``."""
    return [s for s in by_seq(events.spans())
            if s.seq > seen and compiled(s)]


def last_seq():
    return max((s.seq for s in events.spans()), default=0)


def test_nested_traces_file_one_span():
    """A jit over blocks of ``jnp`` functions traces each of them inside
    its own trace: one ``compile.trace`` span for the whole, which counts
    the nested ones."""
    compiles.monitor()

    def blocks(x):
        for _ in range(20):
            x = jnp.tanh(jnp.sin(x) * jnp.cos(x)) + jnp.where(x > 0, x, -x)
        return x
    seen = last_seq()
    jax.jit(blocks)(numpy.ones((3, 17), numpy.float32)).block_until_ready()
    traces = [s for s in filed_since(seen)
              if s.name == "veles.compile.trace"]
    assert [s.info["fun"] for s in traces] == ["blocks"]
    # sin, cos, tanh, where a block at least (jnp's jits; others may be
    # cached from earlier traces of this process at these shapes)
    assert traces[0].info["nested"] >= 1
    assert events.totals()["veles.compile.trace"]["nested"] \
        >= traces[0].info["nested"]


def test_a_lowering_rule_s_traces_are_the_lowering_s():
    """The PRNG's lowering rule is written in ``jnp``: what it traces
    while the program lowers is counted in ``compile.lower``'s
    ``traces``, and no ``compile.trace`` span lies inside the lowering."""
    compiles.monitor()
    key = jax.random.key(3)
    seen = last_seq()

    def noise(key):
        return jax.random.normal(key, (5, 31))
    jax.jit(noise)(key).block_until_ready()
    filed = filed_since(seen)
    (lower,) = [s for s in filed if s.name == "veles.compile.lower"
                and s.info["module"] == "jit(noise)"]
    assert lower.info["traces"] >= 1
    end = lower.start_ns + lower.duration_ns
    assert not [s for s in filed if s.name == "veles.compile.trace"
                and lower.start_ns <= s.start_ns < end]
    assert events.totals()["veles.compile.lower"]["traces"] \
        >= lower.info["traces"]


def test_two_monitors_asked_for_are_one():
    """Asking again, the device's set-up among others, gives the same
    monitor, and each of JAX's phases files one span."""
    first = compiles.monitor()
    backends.apply_compilation_cache_config()
    assert compiles.monitor() is first
    seen = last_seq()
    jax.jit(lambda x: x - 0.5)(numpy.ones(19, numpy.float32))
    names = [s.name for s in filed_since(seen)]
    assert names == ["veles.compile.trace", "veles.compile.lower",
                     "veles.compile.xla"]


def test_two_threads_keep_their_own_parents():
    """Two threads compiling at once: each compile is filed under the
    span its own thread has open."""
    compiles.monitor()
    barrier = threading.Barrier(2, timeout=30)
    outers = {}

    def work(scale):
        with events.timed("probe.thread", scale=scale) as outer:
            outers[scale] = outer
            barrier.wait()
            jax.jit(lambda x: x * scale + 0.125)(
                numpy.ones(23 + scale, numpy.float32)).block_until_ready()
    seen = last_seq()
    threads = [threading.Thread(target=work, args=(scale,))
               for scale in (1, 2)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join(timeout=60)
    assert not any(thread.is_alive() for thread in threads)
    filed = filed_since(seen)
    assert len(filed) == 6
    for scale, outer in outers.items():
        mine = [s for s in filed if s.parent == outer.seq]
        assert [s.name for s in mine] == [
            "veles.compile.trace", "veles.compile.lower",
            "veles.compile.xla"]
        assert all(s.thread == outer.thread for s in mine)


PERSISTED = r"""
import json, sys
import jax, numpy
from veles_tpu.backends import compiles_not_persisted
from veles_tpu.logger import events
from veles_tpu.observability import compiles
compiles.monitor()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
x = numpy.ones(29, numpy.float32)
def scaled(x):
    return x * 7 + 2
jax.jit(scaled)(x).block_until_ready()
jax.clear_caches()          # only the persistent cache is left
jax.jit(scaled)(x).block_until_ready()
with compiles_not_persisted():
    jax.jit(lambda x: x * 11 - 3)(x).block_until_ready()
print(json.dumps([[s.name, s.info] for s in events.spans()
                  if s.name in ("veles.compile.xla",
                                "veles.compile.cache_load")]))
"""


@pytest.fixture(scope="module")
def persisted(tmp_path_factory):
    """(the cache's directory, ``[[name, info]]`` of the backend
    compiles) of a process whose JAX cache lies in a temporary
    directory: a program compiled, dropped from memory and compiled
    again, then one under ``compiles_not_persisted``."""
    tmp_path = tmp_path_factory.mktemp("jax_cache")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", PERSISTED], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return tmp_path, json.loads(out.stdout.strip().splitlines()[-1])


def test_a_program_compiled_twice_is_loaded_from_jax_s_cache(persisted):
    directory, found = persisted
    assert [name for name, _ in found[:2]] == [
        "veles.compile.xla", "veles.compile.cache_load"]
    assert found[0][1] == {"module": "jit(scaled)", "cache": "miss"}
    assert found[1][1] == {"module": "jit(scaled)", "cache": "jax"}
    # a load is no compile: nothing else was compiled or written
    assert len(found) == 3 and os.listdir(directory)


def test_not_persisted_says_off(persisted):
    name, info = persisted[1][-1]
    assert name == "veles.compile.xla" and info["cache"] == "off"
