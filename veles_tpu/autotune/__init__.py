"""veles_tpu.autotune — persistent search over kernel/serving configs.

TVM-style autotuning (arXiv 1802.04799) scaled to this tree: every
Pallas kernel and the serving-geometry knobs declare a small candidate
grid (:mod:`.space`), a runner measures candidates in isolated fresh
subprocesses with hard wall-clock caps and correctness gating
(:mod:`.runner` / :mod:`.probe`), and measured winners persist in a
store keyed by (site, shape-class, device kind, jax/jaxlib versions)
(:mod:`.store`) so tuning is paid once per device generation.  Kernel
call sites resolve through :func:`resolve` with their hand-picked
config as the fallback — with the tuner off (no
``root.common.autotune.dir`` / ``$VELES_AUTOTUNE_DIR``) behavior is
byte-for-byte unchanged.

Drive it with ``tools/autotune.py tune|list|show|verify``.
"""

from .dispatch import (AUTOTUNE_DIR_ENV, default_store,  # noqa: F401
                       reset_default_stores, resolve, resolve_config)
from .runner import measure_candidate, run_isolated, tune_site  # noqa: F401
from .space import SITES, SearchSpace, ladder, site  # noqa: F401
from .store import SCHEMA, SUFFIX, TuningStore, record_key  # noqa: F401
