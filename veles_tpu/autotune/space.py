"""Search spaces: what each tunable site is allowed to try.

TVM framed kernel tuning as search over a declared schedule space
(arXiv 1802.04799); our spaces are far smaller — a handful of block
sizes, layouts, or ladder shapes per site — but the contract is the
same: the site declares *every* candidate up front with validity
constraints, the runner measures, and only a measured, correctness-
gated winner is ever persisted.

Ten builtin sites cover the tree's tunables:

==================== ======================================== ===========
site                 parameters                               dispatch at
==================== ======================================== ===========
lrn                  impl (pallas|mxu), block_rows            znicz/lrn.py
flash_attention      block_q, block_k                         znicz/flash_attention.py
window_attention     block_q, block_k                         znicz/flash_attention.py
precise_gemm         block_m, block_n, block_k                znicz/gemm.py
paged_attention      block_size                               serving/decode.py
serving.bucket_ladder shape (pow2|coarse|dense)               serving/scheduler.py
serving.decode       max_batch, block_size                    serving/decode.py
serving.prefill_chunk chunk_tokens                            serving/decode.py
serving.spec_depth   spec_depth                               serving/decode.py
serving.kv_dtype     kv_dtype (f32|int8)                      serving/decode.py
==================== ======================================== ===========

Every site's ``default`` is the exact hand-picked configuration the
kernel shipped with (cross-checked against the kernel constants in
tests/test_autotune.py), so a resolve with no tuning record — or with
the tuner off — reproduces current behavior byte for byte.

This module imports no JAX: config-time code (CLI ``list``, dispatch
with the tuner off) must stay light.
"""

import itertools

__all__ = ["SearchSpace", "SITES", "site", "ladder", "pow2_bucket"]


def pow2_bucket(n):
    """The next power of two >= n — the shape-class bucket for dims
    that vary continuously (GEMM sizes), so one tuning record covers a
    band of shapes the same blocking serves."""
    n = max(int(n), 1)
    b = 1
    while b < n:
        b <<= 1
    return b


def ladder(shape, max_batch):
    """Materialize a bucket-ladder shape into sizes, largest = max_batch.

    ``pow2`` reproduces ``serving.scheduler.bucket_sizes`` exactly
    (test-enforced — that equality is what makes the tuner-off path
    byte-identical); ``coarse`` trades padding for fewer compiles,
    ``dense`` the reverse (pow2 + 3*2^k midpoints).
    """
    mb = int(max_batch)
    if mb < 1:
        raise ValueError("max_batch must be >= 1")
    if shape == "pow2":
        sizes, b = [], 1
        while b < mb:
            sizes.append(b)
            b <<= 1
        sizes.append(mb)
        return sizes
    if shape == "coarse":
        return sorted({1, max(mb // 4, 1), max(mb // 2, 1), mb})
    if shape == "dense":
        sizes, b = {mb}, 1
        while b < mb:
            sizes.add(b)
            if 3 * b // 2 < mb and b > 1:
                sizes.add(3 * b // 2)
            b <<= 1
        return sorted(sizes)
    raise ValueError("unknown ladder shape %r" % (shape,))


class SearchSpace:
    """One tunable site: parameter grid + validity constraint.

    ``params`` maps parameter name -> tuple of candidate values;
    ``default`` is the hand-picked config (always a valid candidate and
    always measured first — it is the baseline every speedup is
    reported against).  ``constraint(config, ctx)`` filters the cross
    product; ``classify(ctx)`` maps a concrete call context to the
    shape-class string the tuning store keys on.

    ``error_bound`` declares the numeric tolerance a LOSSY candidate
    must stay within to pass the probe's gate (e.g. logit RMSE for
    quantized KV pools).  ``None`` — every site that searches exact
    reformulations — keeps the gate bitwise/exact: an error bound is a
    property of the site's contract, declared here, never improvised
    per probe run.
    """

    def __init__(self, name, params, default, constraint=None,
                 classify=None, description="", error_bound=None):
        self.name = name
        self.params = {k: tuple(v) for k, v in params.items()}
        self.default = dict(default)
        self._constraint = constraint
        self._classify = classify
        self.description = description
        self.error_bound = (None if error_bound is None
                            else float(error_bound))

    def valid(self, config, ctx=None):
        if set(config) != set(self.params):
            return False
        if any(config[k] not in self.params[k] for k in config):
            return False
        return self._constraint(config, ctx or {}) \
            if self._constraint else True

    def candidates(self, ctx=None):
        """Every valid config, hand-picked default FIRST (the runner
        measures it as the baseline even when invalid-by-constraint —
        it is what ships, so it is always comparable)."""
        ctx = ctx or {}
        out = [dict(self.default)]
        names = sorted(self.params)
        for values in itertools.product(*(self.params[n] for n in names)):
            cfg = dict(zip(names, values))
            if cfg == self.default or not self.valid(cfg, ctx):
                continue
            out.append(cfg)
        return out

    def shape_class(self, ctx):
        """The store key's shape-class string for a call context."""
        if self._classify is None:
            return "any"
        return self._classify(ctx or {})


def _lrn_constraint(cfg, ctx):
    # block_rows only means something to the pallas layout; pin it to
    # the default for the mxu band so the grid has no duplicate points
    if cfg["impl"] == "mxu":
        return cfg["block_rows"] == 1024
    rows = ctx.get("rows")
    return rows is None or cfg["block_rows"] <= max(int(rows), 8)


def _attention_constraint(cfg, ctx):
    # the kernel fits blocks down to a divisor itself; restricting the
    # grid to exact divisors of T keeps every candidate DISTINCT
    t = ctx.get("t")
    if t is None:
        return True
    return t % cfg["block_q"] == 0 and t % cfg["block_k"] == 0


def _gemm_constraint(cfg, ctx):
    # VMEM estimate: one A tile + one B tile + out/acc/carry scratch
    # (4 [bm, bn] f32 buffers) must fit comfortably (~12 MB of ~16)
    bm, bn, bk = cfg["block_m"], cfg["block_n"], cfg["block_k"]
    return (bm * bk + bk * bn + 4 * bm * bn) * 4 <= 12 << 20


def _decode_constraint(cfg, ctx):
    ctx_len = ctx.get("max_context")
    return ctx_len is None or cfg["block_size"] <= int(ctx_len)


#: the builtin sites; tools/autotune.py ``tune --site`` names these
SITES = {}


def _register(s):
    SITES[s.name] = s
    return s


_register(SearchSpace(
    "lrn",
    params={"impl": ("pallas", "mxu"),
            "block_rows": (256, 512, 1024, 2048, 4096)},
    # the hand-picked pallas config (lrn._LRN_BLOCK_ROWS); "mxu" is the
    # banded-matmul LAYOUT as a searchable candidate — the measured
    # answer to the old record's 0.6x (docs/PERF.md): on device classes
    # where the pallas_call fusion boundary loses, the tuner picks the
    # band
    default={"impl": "pallas", "block_rows": 1024},
    constraint=_lrn_constraint,
    classify=lambda ctx: "c%d_n%d" % (ctx["c"], ctx.get("n", 5)),
    description="cross-channel LRN: pallas row-tile size, or the "
                "banded-matmul layout"))

_register(SearchSpace(
    "flash_attention",
    params={"block_q": (128, 256, 512), "block_k": (128, 256, 512)},
    default={"block_q": 256, "block_k": 256},   # DEFAULT_BLOCK_Q/K
    constraint=_attention_constraint,
    classify=lambda ctx: "t%d_d%d%s" % (
        pow2_bucket(ctx["t"]), ctx["d"],
        "_causal" if ctx.get("causal") else ""),
    description="flash attention Q/K tile sizes"))

_register(SearchSpace(
    "window_attention",
    params={"block_q": (128, 256, 512), "block_k": (128, 256, 512)},
    default={"block_q": 256, "block_k": 256},
    constraint=_attention_constraint,
    classify=lambda ctx: "t%d_d%d_w%d" % (
        pow2_bucket(ctx["t"]), ctx["d"], ctx.get("window", 0)),
    description="sliding-window attention Q/K tile sizes"))

_register(SearchSpace(
    "precise_gemm",
    params={"block_m": (128, 256, 512), "block_n": (128, 256, 512),
            "block_k": (128, 256, 512)},
    default={"block_m": 128, "block_n": 128, "block_k": 256},
    constraint=_gemm_constraint,
    classify=lambda ctx: "m%d_k%d_n%d_l%d" % (
        pow2_bucket(ctx["m"]), pow2_bucket(ctx["k"]),
        pow2_bucket(ctx["n"]), ctx.get("level", 1)),
    description="compensated-GEMM M/N/K tile sizes"))

_register(SearchSpace(
    "paged_attention",
    params={"block_size": (4, 8, 16, 32)},
    default={"block_size": 8},       # paged_attention.DEFAULT_BLOCK_SIZE
    constraint=_decode_constraint,
    classify=lambda ctx: "h%d_d%d_len%d" % (
        ctx["heads"], ctx["d"], pow2_bucket(ctx.get("max_context", 64))),
    description="KV page size of the ragged paged-attention kernel"))

_register(SearchSpace(
    "serving.bucket_ladder",
    params={"shape": ("pow2", "coarse", "dense")},
    default={"shape": "pow2"},       # scheduler.bucket_sizes
    classify=lambda ctx: "mb%d" % ctx["max_batch"],
    description="bucket-ladder shape: padding waste vs compile count"))

_register(SearchSpace(
    "serving.decode",
    params={"max_batch": (4, 8, 16, 32), "block_size": (4, 8, 16, 32)},
    default={"max_batch": 8, "block_size": 8},
    constraint=_decode_constraint,
    classify=lambda ctx: "ctx%d" % pow2_bucket(ctx.get("max_context", 64)),
    description="decode scheduler geometry: concurrent rows + KV page "
                "size"))


def _chunk_constraint(cfg, ctx):
    # a chunk larger than the prompt ceiling degenerates to monolithic
    # prefill with extra padding — keep candidates distinct
    mp = ctx.get("max_prompt_len")
    return mp is None or cfg["chunk_tokens"] <= pow2_bucket(mp)


_register(SearchSpace(
    "serving.prefill_chunk",
    params={"chunk_tokens": (8, 16, 32, 64)},
    default={"chunk_tokens": 32},    # decode.DEFAULT_PREFILL_CHUNK
    constraint=_chunk_constraint,
    classify=lambda ctx: "mp%d" % pow2_bucket(
        ctx.get("max_prompt_len", 64)),
    description="prefill chunk size: short-request TTFT under "
                "head-of-line long prefills vs per-chunk dispatch "
                "overhead"))


def _spec_constraint(cfg, ctx):
    # speculating past the per-request token budget only writes
    # positions the accept step must discard — keep candidates distinct
    mn = ctx.get("max_new_tokens")
    return mn is None or cfg["spec_depth"] < max(int(mn), 2)


_register(SearchSpace(
    "serving.spec_depth",
    params={"spec_depth": (1, 2, 3, 4, 6, 8)},
    default={"spec_depth": 2},       # decode.DEFAULT_SPEC_DEPTH
    constraint=_spec_constraint,
    classify=lambda ctx: "mn%d" % pow2_bucket(
        ctx.get("max_new_tokens", 32)),
    description="speculative decoding depth: draft tokens per "
                "iteration — measured acceptance rate vs the "
                "multi-token verify pass's cost"))


_register(SearchSpace(
    "serving.kv_dtype",
    params={"kv_dtype": ("f32", "int8")},
    default={"kv_dtype": "f32"},     # decode pools exactly as shipped
    classify=lambda ctx: "ctx%d" % pow2_bucket(
        ctx.get("max_context", 64)),
    error_bound=1e-2,
    description="KV-pool precision: f32 pools exactly as shipped, or "
                "int8 blocks dequantized in-kernel — the first lossy "
                "site, gated on the declared logit-RMSE bound instead "
                "of bitwise equality"))


def site(name):
    try:
        return SITES[name]
    except KeyError:
        raise KeyError("unknown autotune site %r (known: %s)"
                       % (name, ", ".join(sorted(SITES))))
