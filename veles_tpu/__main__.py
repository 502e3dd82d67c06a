"""Command line interface: ``python -m veles_tpu <workflow.py> [config.py]``.

TPU-native re-creation of /root/reference/veles/__main__.py:136-726.  The
capability surface kept from the reference CLI:

- workflow module loading by file path or dotted module name
  (reference import_file.py:50,66), config file application, then
  ``root.x.y=value`` command-line overrides (reference __main__.py:432-478);
- the ``run(load, main)`` module convention (reference
  manualrst_veles_workflow_creation.rst:30-39, __main__.py:591-726);
- ``--snapshot`` resume (reference __main__.py:539-589 — file source; odbc/
  http sources intentionally dropped in the zero-egress build);
- deterministic seeding via ``--random-seed`` (reference :483-539);
- ``--dry-run`` levels load/init/exec (reference cmdline.py);
- ``--result-file``, ``--dump-config``, ``--visualize`` (dot graph);
- backend selection ``--backend`` (reference ``-a/--accelerator``).

TPU-native additions (replacing the master/slave flags): ``--mesh
data=8,model=2`` + ``--model-axis`` request an SPMD run over a device
mesh; ``--mode fused|graph|scan`` picks the execution strategy
(SURVEY.md §7 design stance).
"""

import argparse
import ast
import importlib
import importlib.util
import json
import os
import sys

from .config import root, fix_config, set_config_by_path
from .launcher import Launcher
from .logger import events


def _parse_value(text):
    """Parse an override value: python literal if possible, else string."""
    try:
        return ast.literal_eval(text)
    except (ValueError, SyntaxError):
        return text


def import_workflow_module(spec):
    """Import a workflow module from a file path or dotted module name
    (reference import_file.py:50-66 package-or-module logic).  A file that
    lives inside a package tree (``__init__.py`` chain) is imported by its
    dotted name so its relative imports resolve."""
    if not os.path.exists(spec):
        if "." not in spec:
            # bare name: prefer the bundled sample of that name
            # ("veles-tpu mnist" just works from an installed package)
            sample = "veles_tpu.znicz.samples." + spec
            try:
                return importlib.import_module(sample)
            except ModuleNotFoundError as e:
                if e.name != sample:
                    raise  # a BROKEN sample must not be masked as absent
        return importlib.import_module(spec)
    path = os.path.abspath(spec)
    name = os.path.splitext(os.path.basename(path))[0]
    # climb the package chain
    parts, d = [name], os.path.dirname(path)
    while os.path.exists(os.path.join(d, "__init__.py")):
        parts.insert(0, os.path.basename(d))
        d = os.path.dirname(d)
    if len(parts) > 1:
        if d not in sys.path:
            sys.path.insert(0, d)
        return importlib.import_module(".".join(parts))
    module_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(module_spec)
    sys.modules[name] = module
    module_spec.loader.exec_module(module)
    return module


def apply_config_file(path):
    """Execute a config file with ``root`` (and ``Range``, for GA
    tuneables) in scope (the reference runpy convention, __main__.py:432;
    reference configs imported veles.genetics.Range the same way)."""
    from .config import Range
    with open(path) as f:
        source = f.read()
    exec(compile(source, path, "exec"), {"root": root, "Range": Range})


def parse_seed(spec):
    """--random-seed value → int: decimal, ``0x``/bare hex, or
    ``file:N`` (N bytes read from the file, e.g. ``/dev/urandom:16``) —
    the reference's seeding spec surface (__main__.py:483-539)."""
    spec = str(spec)
    if ":" in spec and not spec.lower().startswith("0x"):
        fname, _, count = spec.rpartition(":")
        try:
            n = int(count)
            with open(fname, "rb") as f:
                data = f.read(n)
        except (ValueError, OSError) as e:
            raise SystemExit("bad --random-seed %r (%s)" % (spec, e))
        if len(data) < n:
            raise SystemExit("--random-seed %r: %s has only %d bytes"
                             % (spec, fname, len(data)))
        return int.from_bytes(data, "little") % (1 << 63)
    try:
        return int(spec, 0)     # decimal or 0x-prefixed hex
    except ValueError:
        try:
            return int(spec, 16)  # bare hex digest (reference unhexlify)
        except ValueError:
            raise SystemExit(
                "bad --random-seed %r (want an int, hex, or file:N)"
                % spec)


def parse_mesh(text):
    """``data=8,model=2`` → {"data": 8, "model": 2}."""
    axes = {}
    for part in text.split(","):
        name, _, size = part.partition("=")
        if not size:
            raise argparse.ArgumentTypeError(
                "mesh axis %r needs =SIZE" % part)
        axes[name.strip()] = int(size)
    return axes


def make_parser():
    p = argparse.ArgumentParser(
        prog="veles_tpu",
        description="TPU-native VELES: run a workflow module.")
    p.add_argument("workflow", nargs="?",
                   help="workflow module (.py path or dotted name)")
    p.add_argument("config", nargs="?",
                   help="config file applied before overrides")
    p.add_argument("overrides", nargs="*", metavar="root.x.y=value",
                   help="config overrides")
    p.add_argument("-s", "--snapshot", default=None,
                   help="resume from a snapshot file")
    p.add_argument("--random-seed", type=str, default=None,
                   metavar="N|0xHEX|PATH:NBYTES",
                   help="seed for the deterministic PRNG tree "
                        "(decimal, hex, or NBYTES read from PATH, "
                        "e.g. /dev/urandom:16 — see parse_seed)")
    p.add_argument("-a", "--backend", default=None,
                   choices=("auto", "tpu", "cpu", "numpy"),
                   help="compute backend (default: config)")
    p.add_argument("--mode", default=None,
                   choices=("fused", "graph", "scan"),
                   help="execution strategy (default: workflow's)")
    p.add_argument("--mesh", type=parse_mesh, default=None,
                   metavar="data=8[,model=2]",
                   help="SPMD device mesh axes")
    p.add_argument("--model-axis", default=None,
                   help="mesh axis for tensor parallelism")
    p.add_argument("--tp-mode", default=None,
                   choices=("column", "megatron"),
                   help="tensor-parallel layout: column-split every "
                        "layer, or megatron col/row alternation (one "
                        "psum per FC pair instead of a gather per layer)")
    p.add_argument("--set", action="append", default=[], dest="sets",
                   metavar="attr.path=value",
                   help="set a workflow attribute after build/restore "
                        "(e.g. --set decision.max_epochs=50); the way to "
                        "extend a resumed run past its pickled limits")
    p.add_argument("--dry-run", default="exec",
                   choices=("load", "init", "exec"),
                   help="stop after load/init (default: full run)")
    p.add_argument("--result-file", default=None,
                   help="write gathered results JSON here ('-' = stdout)")
    p.add_argument("--dump-config", action="store_true",
                   help="print the effective config tree and exit")
    p.add_argument("--visualize", default=None, metavar="FILE.dot",
                   help="write the unit graph in dot format")
    p.add_argument("--stats", action="store_true",
                   help="print per-unit timing stats after the run")
    p.add_argument("--profile", action="store_true",
                   help="attach the StepProfiler (data-wait/host/device "
                        "step split, recompile count, examples/sec into "
                        "/metrics and the result JSON; equivalent to "
                        "root.common.observability.profile=True)")
    p.add_argument("--profiler-port", type=int, default=None, metavar="N",
                   help="serve the JAX profiler on port N "
                        "(jax.profiler.start_server): capture a trace of "
                        "the running trainer with XProf or TensorBoard; "
                        "the program's veles.* spans and the layer scopes "
                        "are in it")
    p.add_argument("--no-fix-config", action="store_true",
                   help="keep Range placeholders (genetic optimizer use)")
    from .cmdline import contribute_arguments
    p._veles_arg_paths = contribute_arguments(p)
    p.add_argument("--death-probability", type=float, default=0.0,
                   help="fault injection: crash with this probability at "
                        "each epoch end (reference "
                        "--slave-death-probability)")
    p.add_argument("--die-at-epoch", type=int, default=None,
                   help="fault injection: crash deterministically at this "
                        "epoch end (elastic-recovery drills)")
    p.add_argument("--optimize", default=None, metavar="SIZE[:GENERATIONS]",
                   help="GA-optimize the config's Range values by running "
                        "trials as subprocesses (reference --optimize)")
    p.add_argument("--fitness-key", default="best_validation_error_pt",
                   help="result JSON key minimized by --optimize")
    p.add_argument("--ensemble-train", default=None, metavar="SIZE[:RATIO]",
                   help="train SIZE instances on random train subsets "
                        "(reference --ensemble-train size:ratio)")
    p.add_argument("--ensemble-test", default=None, metavar="FILE.json",
                   help="averaged-probability inference over the "
                        "ensemble train output JSON")
    p.add_argument("--serve", action="append", default=[],
                   metavar="PKG.zip[:NAME]", dest="serve",
                   help="serve exported package(s) over HTTP with "
                        "dynamic batching instead of training "
                        "(repeatable; NAME defaults to the file stem); "
                        "see veles_tpu.serving")
    p.add_argument("--serve-port", type=int, default=8080,
                   help="inference server port (default 8080)")
    p.add_argument("--serve-hostname", default="127.0.0.1",
                   help="inference server bind address (loopback "
                        "default keeps the models private)")
    p.add_argument("--serve-max-batch", type=int, default=64,
                   help="largest request batch bucket (power-of-two "
                        "ladder compiled at startup)")
    p.add_argument("--serve-queue-limit", type=int, default=256,
                   help="outstanding-request bound; beyond it requests "
                        "are shed with HTTP 429")
    p.add_argument("--serve-workers", type=int, default=1,
                   help="dispatch worker threads per model")
    p.add_argument("--serve-seconds", type=float, default=None,
                   help="serve for N seconds then drain and exit "
                        "(default: until SIGINT; smoke tests/CI)")
    p.add_argument("--frontend", action="store_true",
                   help="interactive wizard: answer prompts, get the "
                        "generated command line, run it (reference "
                        "--frontend web wizard, terminal edition)")
    p.add_argument("--listen", default=None, metavar="HOST:PORT",
                   help="farm --optimize/--ensemble-train trials through "
                        "a TCP job master bound here; start workers on "
                        "any host with `python -m veles_tpu.jobserver "
                        "HOST PORT` (reference master -l role)")
    p.add_argument("--workers", type=int, default=0, metavar="N",
                   help="also spawn N local trial worker processes "
                        "(elastic: dead workers respawn with backoff)")
    return p


class Main:
    """CLI driver implementing the reference ``run(load, main)`` contract
    (reference __main__.py:136,591-726)."""

    def __init__(self, argv=None):
        parser = make_parser()
        self.args = parser.parse_args(argv)
        self._arg_paths = parser._veles_arg_paths
        self.launcher = None
        self.workflow = None
        self.snapshot_loaded = False

    # -- the two callbacks handed to the workflow module ---------------------
    def _load(self, factory, **kwargs):
        """Build the workflow (or restore it from ``--snapshot``); returns
        (workflow, was_restored)."""
        with events.timed("main.load"):
            return self._build(factory, **kwargs)

    def _build(self, factory, **kwargs):
        args = self.args
        if args.snapshot:
            if args.mesh or args.model_axis or args.mode or args.tp_mode:
                raise SystemExit(
                    "--mesh/--model-axis/--tp-mode/--mode cannot be "
                    "applied to a restored snapshot (the pickled "
                    "workflow keeps its build-time execution strategy); "
                    "rebuild without --snapshot, or restore and resume "
                    "as-is")
            from .snapshotter import restore
            self.workflow = restore(args.snapshot)
            self.snapshot_loaded = True
        else:
            if args.mode == "graph":
                kwargs.setdefault("fused", False)
            elif args.mode == "scan":
                kwargs.setdefault("epoch_scan", True)
            elif args.mode == "fused":
                kwargs.setdefault("fused", True)
            if args.mesh:
                from .parallel.mesh import make_mesh
                kwargs.setdefault("mesh", make_mesh(args.mesh))
                if args.model_axis:
                    kwargs.setdefault("model_axis", args.model_axis)
                if args.tp_mode:
                    kwargs.setdefault("tp_mode", args.tp_mode)
            self.workflow = factory(**kwargs)
        for assignment in args.sets:
            path, _, value = assignment.partition("=")
            if not value:
                raise SystemExit("--set %r needs =value" % assignment)
            obj = self.workflow
            parts = path.split(".")
            for p in parts[:-1]:
                obj = getattr(obj, p)
            setattr(obj, parts[-1], _parse_value(value))
        if args.death_probability or args.die_at_epoch is not None:
            from .distributed import Reaper
            wf = self.workflow
            reaper = next((u for u in wf if isinstance(u, Reaper)), None)
            if reaper is None and hasattr(wf, "decision") and \
                    hasattr(wf, "loader"):
                from .prng import RandomGenerator
                # own seeded stream: drills replay under --random-seed
                # without consuming the loaders' stream
                seed = (parse_seed(args.random_seed)
                        if args.random_seed is not None else 1234) + 313
                reaper = Reaper(wf, prng=RandomGenerator().seed(seed))
                reaper.link_from(wf.decision)
                reaper.link_loader(wf.loader)
            if reaper is not None:
                reaper.death_probability = args.death_probability
                reaper.die_at_epoch = args.die_at_epoch
        self.launcher.add_workflow(self.workflow)
        return self.workflow, self.snapshot_loaded

    def _main(self, **kwargs):
        args = self.args
        if args.dry_run == "load":
            return self.workflow
        if args.profile:
            root.common.observability.profile = True
        with events.timed("main.initialize"):
            self.launcher.initialize(**kwargs)
        if args.visualize:
            self.workflow.generate_graph(args.visualize)
        if args.dry_run == "init":
            return self.workflow
        self.launcher.run()
        if args.stats:
            self.launcher.print_stats()
        return self.workflow

    # -- entry ---------------------------------------------------------------
    def run(self):
        args = self.args
        if args.serve:
            return self._run_serve()
        if args.frontend:
            return self._run_frontend()
        if args.config is not None and "=" in args.config \
                and not os.path.exists(args.config):
            # `workflow.py root.x=1` without a config file
            args.overrides.insert(0, args.config)
            args.config = None
        if args.ensemble_test:
            # pure aggregation over an existing ensemble JSON — no
            # workflow module involved
            from . import ensemble
            self._write_result(ensemble.test(args.ensemble_test))
            return 0
        if not args.workflow:
            if args.dump_config:
                root.print_()
                return 0
            make_parser().print_help()
            return 2
        # the module import registers the workflow's config DEFAULTS; the
        # config file, then the CLI overrides, are applied on top of them
        # (reference order: _load_model :401 before _apply_config :432)
        module = import_workflow_module(args.workflow)
        # machine-local site_config lands AFTER the module's defaults
        # (so a site file can actually override them) and BEFORE the
        # config file / CLI overrides (which stay the most specific).
        # The reference applied site files at config-import time, which
        # let module defaults clobber them (config.py:294-308) — this
        # order is the deliberate improvement.
        from .config import apply_site_config
        apply_site_config()
        if args.config:
            apply_config_file(args.config)
        for override in args.overrides:
            path, _, value = override.partition("=")
            if not value:
                raise SystemExit("override %r needs =value" % override)
            set_config_by_path(root, path, _parse_value(value))
        # class-contributed options (reference cmdline.py distributed
        # argparse) — applied LAST so an explicit flag beats config files
        from .cmdline import apply_arguments
        apply_arguments(args, self._arg_paths, set_config_by_path, root)
        if args.optimize or args.ensemble_train:
            return self._run_meta(module)
        if args.listen or args.workers:
            raise SystemExit(
                "--listen/--workers distribute --optimize/--ensemble-train "
                "trials; pass one of those meta flags (a plain training "
                "run is a single process — use --mesh for multi-chip)")
        if not args.no_fix_config:
            fix_config(root)
        if args.dump_config:
            root.print_()
            return 0
        seed = args.random_seed
        if seed is None:
            seed = root.common.get("random_seed", 1234)
        from . import prng
        prng.get(0).seed(parse_seed(seed))
        self.launcher = Launcher(backend=args.backend,
                                 result_file=args.result_file,
                                 profiler_port=args.profiler_port)
        if not hasattr(module, "run"):
            raise SystemExit(
                "workflow module %r does not define run(load, main)"
                % args.workflow)
        module.run(self._load, self._main)
        wf = self.workflow
        if wf is not None and args.dry_run == "exec" and not wf.is_finished:
            return 1  # unit queue drained without reaching the end point
        return 0


    def _run_serve(self, output=print):
        """``--serve pkg.zip`` mode: stand up the dynamic-batching
        inference server on the exported package(s) and block until
        SIGINT (or ``--serve-seconds``), then drain gracefully.  The
        train-side flags don't apply; ``--backend`` still picks the
        JAX platform the executables compile for."""
        args = self.args
        # with --serve there is no workflow module, so positional args
        # shift: `root.x=v` strings (and a config file) slide from the
        # workflow/config slots into the override list
        for slot in ("config", "workflow"):
            value = getattr(args, slot)
            if value is not None and "=" in value \
                    and not os.path.exists(value):
                args.overrides.insert(0, value)
                setattr(args, slot, None)
        if args.workflow:
            raise SystemExit("--serve serves exported packages; drop "
                             "the workflow argument (train first, "
                             "export with veles_tpu.export, then serve "
                             "the package zip)")
        if args.backend and args.backend not in ("auto", "numpy"):
            import jax
            jax.config.update("jax_platforms", args.backend)
        # config overrides apply in serve mode too — that's how the
        # compile cache is pointed at its directory from the CLI
        # (`root.common.compile_cache={'dir': ...}`); a config file in
        # the shifted positional slot applies first, overrides on top
        if args.config:
            apply_config_file(args.config)
        for override in args.overrides:
            path, _, value = override.partition("=")
            if not value:
                raise SystemExit("override %r needs =value" % override)
            set_config_by_path(root, path, _parse_value(value))
        from .serving import InferenceServer
        models = []
        for spec in args.serve:
            path, _, name = spec.partition(":")
            if not name:
                name = os.path.splitext(os.path.basename(path))[0]
            models.append((name, path))
        # models register (and warmup-compile their bucket ladders)
        # BEFORE the socket opens: the first request ever seen is
        # already warm, and /healthz never advertises an empty server
        server = InferenceServer(
            models, port=args.serve_port, host=args.serve_hostname,
            max_batch=args.serve_max_batch,
            queue_limit=args.serve_queue_limit,
            workers=args.serve_workers)
        try:
            for name, path in models:
                entry = server.registry.get(name)
                output("serving %r from %s  (buckets %s)  POST %s/api/%s"
                       % (name, path, entry.scheduler.buckets,
                          server.url, name))
            output("endpoints: POST %s/api  ·  GET %s/healthz  ·  "
                   "GET %s/metrics" % (server.url, server.url, server.url))
            try:
                import threading
                threading.Event().wait(args.serve_seconds)
            except KeyboardInterrupt:
                output("draining...")
        finally:
            server.stop(drain=True)
        return 0

    def _run_frontend(self, input_fn=input, output=print):
        """Terminal wizard: prompt for the run's pieces, print the
        generated command line, execute it (the reference's --frontend
        opened a web wizard that produced a command line the same way,
        __main__.py:258-285)."""
        def ask(prompt, default=""):
            try:
                answer = input_fn("%s%s: " % (
                    prompt, " [%s]" % default if default else ""))
            except EOFError:
                return default
            return answer.strip() or default

        argv = []
        workflow = ask("Workflow module/file", self.args.workflow or "")
        if not workflow:
            raise SystemExit("--frontend needs a workflow to run")
        argv.append(workflow)
        config = ask("Config file (blank = none)")
        if config:
            argv.append(config)
        while True:
            override = ask("Override root.x.y=value (blank = done)")
            if not override:
                break
            if "=" not in override:
                output("  ignored (need path=value): %s" % override)
                continue
            argv.append(override)
        backend = ask("Backend (auto/tpu/cpu/numpy)", "auto")
        if backend and backend != "auto":
            argv += ["--backend", backend]
        mode = ask("Execution mode (fused/scan/graph)", "fused")
        if mode and mode != "fused":
            argv += ["--mode", mode]
        seed = ask("Random seed", "1234")
        if seed:
            argv += ["--random-seed", seed]
        result_file = ask("Result JSON file (blank = none)")
        if result_file:
            argv += ["--result-file", result_file]
        import shlex
        output("Running with the following command line: "
               "python -m veles_tpu %s" % shlex.join(argv))
        if ask("Proceed? (y/n)", "y").lower() not in ("y", "yes"):
            return 2
        return Main(argv).run()

    # -- meta modes: GA optimization and ensembles ---------------------------
    def _trial_argv(self):
        """CLI arguments each subprocess trial inherits (config file,
        overrides, backend/mode — NOT the meta flags themselves)."""
        args = self.args
        argv = []
        if args.config:
            # trials run with cwd=repo root (subproc.run_trial); a
            # relative config path from the user's cwd must survive that
            argv.append(os.path.abspath(args.config))
        argv += args.overrides
        if args.backend:
            argv += ["--backend", args.backend]
        if args.mode:
            argv += ["--mode", args.mode]
        if args.mesh:
            argv += ["--mesh", ",".join("%s=%d" % kv
                                        for kv in args.mesh.items())]
        if args.model_axis:
            argv += ["--model-axis", args.model_axis]
        if args.tp_mode:
            argv += ["--tp-mode", args.tp_mode]
        if args.snapshot:
            argv += ["--snapshot", args.snapshot]
        for assignment in args.sets:
            argv += ["--set", assignment]
        if args.random_seed is not None:
            # forward the RESOLVED int, not the spec: a PATH:NBYTES
            # spec (e.g. /dev/urandom:16) re-read per trial would give
            # every trial a different seed, breaking the determinism
            # guarantee trials rely on
            argv += ["--random-seed",
                     str(parse_seed(args.random_seed))]
        # class-contributed flags travel as config overrides so trials
        # see them too (the flags themselves are parsed per process)
        for dest, path in self._arg_paths.items():
            value = getattr(args, dest, None)
            if value is not None:
                argv.append("%s=%r" % (path, value))
        return argv

    def _write_result(self, payload):
        args = self.args
        text = json.dumps(payload, indent=2)
        if args.result_file and args.result_file != "-":
            with open(args.result_file, "w") as f:
                f.write(text)
        else:
            print(text)

    def _run_meta(self, module):
        """Dispatch --optimize / --ensemble-train (--ensemble-test is
        handled earlier in run(): it needs no workflow module).  The
        reference ran these same meta-workflows by re-invoking its own
        CLI per trial (optimization_workflow.py:286-296,
        ensemble/base_workflow.py:134-141).  With --listen/--workers the
        trials go through the cross-host job queue (jobserver.py)."""
        args = self.args
        scheduler = pool = None
        if args.listen or args.workers:
            from .jobserver import JobMaster, WorkerPool, parse_address
            host, port = parse_address(args.listen) if args.listen \
                else ("127.0.0.1", 0)
            scheduler = JobMaster(host, port, silent=False)
            if args.workers:
                pool = WorkerPool(scheduler.address, args.workers)
        try:
            if args.ensemble_train:
                from . import ensemble
                size, _, ratio = args.ensemble_train.partition(":")
                trial_argv = self._trial_argv()
                if ratio:
                    # an explicit N:ratio is the most specific setting —
                    # strip any --train-ratio-derived override so it wins
                    trial_argv = [
                        a for a in trial_argv if not str(a).startswith(
                            "root.common.ensemble.train_ratio=")]
                out = ensemble.train(
                    args.workflow, int(size),
                    train_ratio=float(ratio) if ratio
                    else (args.train_ratio or 1.0),
                    argv=trial_argv, scheduler=scheduler,
                    out_file=(args.result_file
                              if args.result_file not in (None, "-")
                              else None))
                if args.result_file in (None, "-"):
                    self._write_result(out["summary"])
                return 0
            from .genetics import GeneticsOptimizer
            size, _, gens = args.optimize.partition(":")
            trial_argv = self._trial_argv()
            if args.random_seed is None:
                # trials must still be deterministic relative to each other
                trial_argv += ["--random-seed", "1234"]
            opt = GeneticsOptimizer(
                model=args.workflow, config=root, size=int(size),
                generations=int(gens) if gens else 2,
                fitness_key=args.fitness_key, argv=trial_argv,
                scheduler=scheduler)
            best = opt.run()
            self._write_result(best)
            return 0
        finally:
            # master first: its EOF is what makes idle workers exit 0,
            # so the pool close below reaps them instead of killing them
            if scheduler is not None:
                scheduler.close()
            if pool is not None:
                pool.close()


def main(argv=None):
    return Main(argv).run()


if __name__ == "__main__":
    sys.exit(main())
