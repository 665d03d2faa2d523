"""One cell of the serving benchmark: set-up, the measured window, the
metrics and the check of what was served.

Everything that belongs to one cell, configuration, traffic mix, window
driver or metric sits in a file of its own and is found by its name in
``BENCHMARK.json``: ``cells/<cell>.json``, ``configs/<config>.json``,
``traffic/<mix>.json``, ``drivers/<driver>.py``, ``metrics/<metric>.py`` and
``reference/<family>.py``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import sys
import time
import types
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from . import flops
from .reference import common
from .traffic import generator

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
PKG = __package__

# How a configuration file's published keys map onto the program's
# ModelConfig, per family, and what the program fixes that the file must
# state as run.
FIELDS = {
    "dense": {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads",
              "intermediate_size": "d_ff",
              "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
              "tie_word_embeddings": "tie_embeddings"},
    "ssm": {"n_layer": "n_layers", "d_model": "d_model",
            "d_state": "ssm_state",
            "headdim": "ssm_head_dim", "expand": "ssm_expand",
            "chunk_size": "ssm_chunk", "d_conv": "ssm_conv",
            "norm_epsilon": "norm_eps", "tie_embeddings": "tie_embeddings"},
}
AS_RUN = {
    "dense": lambda c: {
        "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
        "logits_scaling": 1.0,
        "attention_multiplier":
            (c["hidden_size"] // c["num_attention_heads"]) ** -0.5},
    "ssm": lambda c: {"ngroups": 1, "residual_in_fp32": False},
}


@dataclasses.dataclass
class Spec:
    """A cell and the files it names."""
    name: str
    workload: dict
    cell: dict
    config: dict
    mix: dict
    per_layer: list
    end_to_end: list


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path) -> dict:
    return json.loads(Path(path).read_text())


def load_spec(name: str, root: Path = ROOT) -> Spec:
    bench = load_json(root / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = {c["name"]: c for c in bench["configs"]}[wl[name]["config"]]
    return Spec(name=name, workload=wl[name],
                cell=load_json(HERE / "cells" / f"{name}.json"),
                config=load_json(root / conf["file"]),
                mix=load_json(HERE / "traffic" / f"{wl[name]['traffic']}.json"),
                per_layer=[m for m in bench["per_layer"]
                           if name in m.get("workloads", [name])],
                end_to_end=[m for m in bench["end_to_end"]
                            if name in m.get("workloads", [name])])


def program_config(config: dict):
    """The program's ModelConfig for a configuration file; refuses a file
    whose as-run values the program does not compute."""
    from repro.configs import get_config
    fam, c = config["family"], config["config"]
    for k, v in AS_RUN[fam](c).items():
        if c.get(k, v) != v:
            raise ValueError(f"{config['name']}: the program runs {k}={v}, "
                             f"the file states {c[k]}")
    fields = {f: c[k] for k, f in FIELDS[fam].items()}
    return get_config(config["arch"], config["preset"]).replace(
        param_dtype=config["dtype"], vocab=common.vocab_rows(c), **fields)


def check_layout(cfg, params) -> None:
    """The benchmark's weights have exactly the program's tree, shapes and
    dtypes."""
    from repro.models import init_params
    want = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (w.shape, w.dtype) != (g.shape, g.dtype) for w, g in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter layout")


def wave_steps_bound(gen_lens, slots: int) -> int:
    """The most decode steps one ``SlotScheduler.run`` call can take when it
    serves any part of these requests.  Each holds a slot for ``gen_len -
    1`` steps, and a call refills every freed slot while requests wait, so
    it ends within their total over the slots plus the longest of them
    (the bound of list scheduling)."""
    held = [g - 1 for g in gen_lens]
    return -(-sum(held) // slots) + max(held)


def concat_groups(n_max: int, k: int = 16) -> list[tuple[int, ...]]:
    """The operand widths of every ``lax.concatenate`` that
    ``jnp.concatenate`` issues for up to ``n_max`` arrays of width 1: it
    joins them in groups of ``k``, level by level, until one is left."""
    seen = {}
    for n in range(2, n_max + 1):
        widths = [1] * n
        while len(widths) > 1:
            groups = [tuple(widths[i:i + k])
                      for i in range(0, len(widths), k)]
            seen.update(dict.fromkeys(groups))
            widths = [sum(g) for g in groups]
    return list(seen)


def key_for(seed: int):
    """A JAX key from any whole number."""
    return jax.random.PRNGKey(int(generator.rng_for(seed).integers(1 << 31)))


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")
    count = 0
    _registered = False

    @classmethod
    def install(cls):
        if cls._registered:
            return
        cls._registered = True

        def on(name, *args, **kw):
            if name in cls.EVENTS:
                cls.count += 1
        jax.monitoring.register_event_listener(on)
        jax.monitoring.register_event_duration_secs_listener(on)


class DecodeLog:
    """The engine as the scheduler sees it, with the kv bucket of each
    decode call noted on the host; everything else is the engine's."""

    def __init__(self, engine):
        self._engine = engine
        self.buckets: list[int] = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def _decode_quiet(self, toks, cache, bucket):
        self.buckets.append(bucket)
        return self._engine._decode_quiet(toks, cache, bucket)


def _quiet(fn, *args):
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        return fn(*args)


def memory_bytes(devices, stat: str = "peak_bytes_in_use") -> int:
    """A memory statistic of the fullest chip: by default the peak since
    the process started.  ``SlotScheduler.run`` builds its slot bank anew
    in every call, so each wave of the window reaches the peak that set-up
    reached with the same call."""
    return int(max((d.memory_stats() or {}).get(stat, 0) for d in devices))


class Bench:
    """Build a cell once; then run windows and check them."""

    def __init__(self, spec: Spec, seed: int):
        self.spec = spec
        self.seed = seed
        self.model = spec.config["config"]
        self.family = spec.config["family"]
        self.ref = importlib.import_module(
            f"{PKG}.reference.{self.family}")
        self.driver = importlib.import_module(
            f"{PKG}.drivers.{spec.cell['driver']}")
        self.vocab = self.model["vocab_size"]     # prompts draw from it
        self.rows = common.vocab_rows(self.model)  # served tokens from these
        CompileCounter.install()

    # -- set-up ------------------------------------------------------------

    def setup(self, seconds: float) -> None:
        from repro.serve import ServeEngine, SlotScheduler
        cell = self.spec.cell
        self.cfg = program_config(self.spec.config)
        self.params = jax.block_until_ready(
            self.ref.make_weights(self.model, key_for(self.seed)))
        check_layout(self.cfg, self.params)
        self.engine = ServeEngine(self.cfg, self.params,
                                  max_len=cell["max_len"],
                                  kv_block=cell["kv_block"])
        self.decodes = DecodeLog(self.engine)
        self.sched = SlotScheduler(self.decodes, cell["slots"])
        self.warm(seconds)

    def prompt_lens(self) -> list[int]:
        return generator.grid(self.spec.mix["prompt"])

    def buckets(self) -> list[int]:
        """Every kv bucket a decode step of this mix can ask for."""
        g = self.spec.mix["output"]
        lo = min(self.prompt_lens()) + 1
        hi = max(self.prompt_lens()) + g["max"] - 1
        return sorted({self.engine.bucket_for(x) for x in range(lo, hi + 1)})

    def arrivals(self, seconds: float, seed: int) -> list:
        return generator.generate(self.spec.mix, self.spec.cell["rate_per_s"],
                                  seconds, seed, self.vocab)

    def warm(self, seconds: float) -> None:
        """Compile (or load) every program a window of ``seconds`` can run:
        the cache bank and one admission through ``SlotScheduler.run``, one
        direct call of the admission program per prompt length and of the
        decode program per kv bucket, and the concatenations that fetch a
        wave's tokens."""
        from repro.models import init_serve_cache
        from repro.serve import Request
        slots = self.spec.cell["slots"]
        rng = generator.rng_for(self.seed)
        lens = self.prompt_lens()

        def tokens(n):
            return rng.integers(0, self.vocab, (1, n), dtype=np.int32)

        self.sched.run([Request(rid=0, tokens=tokens(lens[0]), gen_len=1)])
        cache = init_serve_cache(self.cfg, slots, self.engine.max_len,
                                 batch={"tokens": jnp.zeros((slots, 1),
                                                            jnp.int32)})
        st = jnp.zeros((slots, 1), jnp.int32)
        for n in lens:
            _, cache, st = _quiet(self.sched._admit, self.params,
                                  jnp.asarray(tokens(n)), {}, cache, st,
                                  np.int32(0))
        for b in self.buckets():
            st, _, cache = self.engine._decode_quiet(st, cache, b)
        # SlotScheduler.run fetches a wave's tokens with one jnp.concatenate
        # of its per-step (slots, 1) arrays: one program per list of
        # operand widths, for every wave length the window can reach.  The
        # lengths are the same for every seed, so the bound is too.
        gen = [a.gen_len for a in self.arrivals(seconds, self.seed)]
        steps = wave_steps_bound(gen, slots)
        t = time.perf_counter()
        ops = {1: st}
        groups = concat_groups(steps)
        for group in groups:
            for w in group:
                if w not in ops:
                    ops[w] = jnp.zeros((slots, w), jnp.int32)
            jax.lax.concatenate([ops[w] for w in group], 1)
        jax.block_until_ready(st)
        log(f"set-up: {len(groups)} concatenations for waves of up to "
            f"{steps} steps in {time.perf_counter() - t:.2f} s")
        del cache, st, ops

    # -- the window -----------------------------------------------------------

    def window(self, seconds: float, seed: int | None = None,
               trace_dir: str | None = None) -> dict:
        """Serve one window of traffic drawn from ``seed``; returns the
        driver's records with the arrivals, the compile count and the kv
        bucket of every decode call."""
        seed = self.seed if seed is None else seed
        arrivals = self.arrivals(seconds, seed)
        self.decodes.buckets.clear()
        gc.collect()
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
        c0 = CompileCounter.count
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("window"):
            out = self.driver.drive(self.sched, arrivals,
                                    clock=time.perf_counter,
                                    sleep=time.sleep,
                                    span=jax.profiler.TraceAnnotation)
        compiles = CompileCounter.count - c0
        if trace_dir is not None:
            jax.profiler.stop_trace()
        out.update(t0=t0, seconds=seconds, seed=seed, arrivals=arrivals,
                   compiles=compiles, buckets=list(self.decodes.buckets))
        return out

    def run_view(self, out: dict, setup_s: float, trace=None,
                 device_kind: str | None = None):
        """What the metric readers see of a window."""
        try:
            peaks = flops.peaks(device_kind) if device_kind else None
        except KeyError:
            peaks = None
        return types.SimpleNamespace(
            records=out["records"], waves=out["waves"],
            lateness_s=out["lateness_s"], seconds=out["seconds"],
            window_end_s=max(r["end_s"] for r in out["records"]),
            compiles=out["compiles"], buckets=out["buckets"],
            setup_s=setup_s, trace=trace, peaks=peaks,
            slots=self.spec.cell["slots"], family=self.family,
            model=self.model)

    # -- the check ---------------------------------------------------------------

    def free_program_state(self) -> None:
        """Drop the engine and scheduler (their caches and compiled
        programs' buffers); the weights stay for the reference."""
        self.sched = self.decodes = self.engine = None
        gc.collect()

    def sample(self, out: dict) -> list[dict]:
        """Requests to compare, drawn from the window's seed: the one with
        the most served tokens, then others in random order until the
        sample holds ``sample_tokens`` served tokens or
        ``sample_requests`` requests."""
        chk = self.spec.cell["check"]
        recs = sorted(out["records"], key=lambda r: (-r["gen_len"],
                                                     -r["prompt_len"],
                                                     r["rid"]))
        rest = generator.rng_for(out["seed"] + 1).permutation(len(recs) - 1)
        chosen = [recs[0]] + [recs[1 + i] for i in rest]
        picked, n = [], 0
        for r in chosen:
            if n >= chk["sample_tokens"] or len(picked) >= chk["sample_requests"]:
                break
            picked.append(r)
            n += r["gen_len"]
        return picked

    def reference_logits(self, prompt, served, matmul_dtype="float32"):
        seq, pos = common.sequence(prompt, served)
        return self.ref.logits(self.params, self.model, seq, pos,
                               matmul_dtype=matmul_dtype)

    def check(self, out: dict, control: bool = False) -> dict:
        """Each number compared, with its limit.  ``requests_incomplete``
        counts requests due in the window that came back short, with a
        token outside the vocabulary, or not at all; ``logit_gap`` is the
        widest gap, over a sample of served tokens, between the float32
        reference's best logit and its logit for the served token.  With
        ``control`` the reading of the reference computed in the cell's
        control precision, at the same positions, is added."""
        recs = out["records"]
        bad = len(out["arrivals"]) - len(recs) + sum(
            1 for r in recs
            if len(r["tokens"]) != r["gen_len"]
            or int(np.min(r["tokens"])) < 0
            or int(np.max(r["tokens"])) >= self.rows)
        prompts = {a.rid: a.tokens for a in out["arrivals"]}
        gaps, ctl = [], []
        for r in self.sample(out):
            ref = self.reference_logits(prompts[r["rid"]], r["tokens"])
            gaps.append(common.served_gap(ref, r["tokens"]))
            if control:
                low = self.reference_logits(
                    prompts[r["rid"]], r["tokens"],
                    self.spec.cell["check"]["control_dtype"])
                ctl.append(common.control_gap(ref, low))
        limit = self.spec.cell["check"]["logit_gap_limit"]
        checks = {"requests_incomplete": {"value": bad, "limit": 0},
                  "logit_gap": {"value": max(gaps), "limit": limit}}
        if control:
            checks["control_logit_gap"] = {"value": max(ctl), "limit": limit}
        return checks


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for k, c in checks.items()
               if c["limit"] is not None and not k.startswith("control"))
