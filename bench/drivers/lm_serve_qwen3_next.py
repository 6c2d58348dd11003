"""Driver of Qwen3-Next serving cells: Gated DeltaNet layers with gated
full attention every few layers and a dropless expert layer holding one
chip's share of the experts, served by the program's continuous-batching
engine.

It is ``lm_serve.py``'s driver with another model: the window, the
records, the sample and the end-to-end numbers are that file's
(``ServingCell.serve``, ``sample``, ``end_to_end``, ``attempted_failed``).
What differs:

* the weights come from ``bench/reference/qwen3_next.py`` and go into the
  tree the program's ``lm.init_params`` gives for the ``gdn`` family;
* the compiled ``bench_prefill_<L>`` and ``bench_decode`` programs also
  return the routing counters of every expert layer (``lm.prefill`` and
  ``lm.decode_step`` with ``routing=True``).  The engine is handed
  callables that return ``(cache, logits)`` as usual and keep each call's
  counters (device arrays, with the call's time); they are read after the
  window, in one transfer, so the window holds no extra sync;
* a traced run attributes the device operations of the traced steps to
  the program's named scopes (``bench/op_scopes.py``) for
  ``gdn_roofline`` and ``moe_roofline``;
* the check compares with ``qwen3_next.Reference``, by the same
  ``mean_logit_gap`` as ``lm_serve.check``.

:func:`control_rows` reads the check's numbers and the float8 control's
over many seeds (``bench/control_qwen3_next.py``).
"""

from __future__ import annotations

import dataclasses
import gc
import math
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import op_scopes, trace_reduce
from bench.drivers import lm_serve as base
from bench.reference import qwen3_next as ref
from bench.work_qwen3_next import Qwen3NextShapes, Routing


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a Qwen3-Next configuration file:
    the Hugging Face keys, with ``num_experts`` the experts held here,
    ``router_experts`` those routed over and ``expert_offset`` the first
    held."""
    from repro.models import ModelConfig

    if int(cfg["shared_expert_intermediate_size"]) != int(cfg["moe_intermediate_size"]):
        raise ValueError(f"{cfg['name']}: the program's shared expert has the routed "
                         "experts' width")
    if int(cfg["linear_key_head_dim"]) != int(cfg["linear_value_head_dim"]):
        raise ValueError(f"{cfg['name']}: the program's DeltaNet heads are square")
    return ModelConfig(
        name=cfg["name"], family="gdn", n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]), n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]), head_dim=int(cfg["head_dim"]),
        d_ff=0, vocab_size=int(cfg["vocab_size"]), qk_norm=True,
        rope_theta=float(cfg["rope_theta"]),
        partial_rotary_factor=float(cfg["partial_rotary_factor"]),
        attn_output_gate=True, norm_zero_centred=True,
        n_experts=int(cfg["router_experts"]), top_k=int(cfg["num_experts_per_tok"]),
        d_expert=int(cfg["moe_intermediate_size"]), shared_expert=True,
        shared_expert_gate=True,
        expert_offset=int(cfg["expert_offset"]), n_local_experts=int(cfg["num_experts"]),
        full_attn_every=int(cfg["full_attention_interval"]),
        lin_k_heads=int(cfg["linear_num_key_heads"]),
        lin_v_heads=int(cfg["linear_num_value_heads"]),
        lin_head_dim=int(cfg["linear_key_head_dim"]),
        conv_width=int(cfg["linear_conv_kernel_dim"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        tie_embeddings=bool(cfg["tie_word_embeddings"])).validate()


def program_tree(w: Dict[str, Any], abstract: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's leaves (``qwen3_next.draw_all``) in the tree
    ``lm.init_params`` returns; embedding and head zero-padded to the
    program's padded vocabulary."""
    import jax.numpy as jnp

    def pad_rows(x, rows):
        return jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)))

    def moe(lw):
        return {"router": lw["router"], "wg": lw["wg"], "wu": lw["wu"], "wd": lw["wd"],
                "shared": {"wg": lw["shared_wg"], "wu": lw["shared_wu"],
                           "wd": lw["shared_wd"]},
                "shared_gate": lw["shared_gate"]}

    lin, full = w["lin"], w["full"]
    vpad = abstract["embed"]["table"].shape[0]
    tree = {
        "embed": {"table": pad_rows(w["embed"], vpad)},
        "final_norm": w["final_norm"],
        "blocks": {
            "lin": {"ln1": lin["ln1"], "ln2": lin["ln2"], "moe": moe(lin),
                    "gdn": {"w_qkvz": lin["w_qkvz"], "w_ba": lin["w_ba"],
                            "conv": lin["conv"], "A_log": lin["A_log"],
                            "dt_bias": lin["dt_bias"], "norm": lin["lin_norm"],
                            "w_out": lin["w_out"]}},
            "full": {"ln1": full["ln1"], "ln2": full["ln2"], "moe": moe(full),
                     "attn": {"wq": full["wq"], "wk": full["wk"], "wv": full["wv"],
                              "wo": full["wo"], "gamma_q": full["q_norm"],
                              "gamma_k": full["k_norm"]}},
        },
    }
    if "unembed" in abstract:
        tree["unembed"] = {"out": pad_rows(w["unembed"].T, vpad).T}
    return tree


class _Counted:
    """A compiled program that returns ``(cache, logits, counters)``,
    called as one that returns ``(cache, logits)``; each call's counters
    are kept with its time in ``notes`` as ``(time, prompt length or 0,
    counters)``."""

    def __init__(self, program, notes: List[Tuple[float, int, Any]], length: int = 0):
        self.program, self.notes, self.length = program, notes, length

    def __call__(self, params, *args):
        cache, logits, counters = self.program(params, *args)
        self.notes.append((time.perf_counter(), self.length, counters))
        return cache, logits


class Qwen3NextCell(base.ServingCell):
    """``lm_serve.ServingCell`` for a Qwen3-Next configuration."""

    def __init__(self, cell):  # noqa: D107 - the base's attributes, another model
        import jax

        from repro.models import lm

        self.cell = cell
        self.cfg = cell.config
        self.spec = cell.traffic
        self.shapes = Qwen3NextShapes.from_config(self.cfg)
        self.mcfg = model_config(self.cfg)
        self.buckets = sorted(int(b) for b in self.spec["prompt_buckets"])
        self.max_out = int(self.spec["output_tokens"]["max"])
        self.max_len = max(self.buckets) + self.max_out + 1
        self.max_batch = int(self.spec["max_batch"])
        self.workers = int(self.spec["workers"])
        self.params = None
        self._lm = lm
        self._jax = jax
        self.compile_s = 0.0
        self.notes: List[Tuple[float, int, Any]] = []
        self.hlo: Dict[str, str] = {}

    def compile(self) -> None:
        jax, lm, mcfg = self._jax, self._lm, self.mcfg
        import jax.numpy as jnp

        t0 = time.perf_counter()
        abstract = lm.abstract_params(mcfg)
        dtype = jnp.dtype(self.cfg["torch_dtype"])

        def make_weights(key):
            return program_tree(ref.draw_all(key, self.cfg, dtype), abstract)

        key0 = ref.dense_lm.seed_key(0)
        self._make = jax.jit(make_weights).lower(key0).compile()
        made = jax.eval_shape(make_weights, key0)
        want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), made)
        if want != got:
            raise ValueError(f"weights do not match lm.abstract_params: {got} != {want}")

        max_len = self.max_len
        self._prefill = {}
        for length in self.buckets:
            def fn(params, tokens):
                return lm.prefill(params, mcfg, {"tokens": tokens}, None, max_len=max_len,
                                  routing=True)
            fn.__name__ = f"bench_prefill_{length}"
            program = jax.jit(fn).lower(
                made, jax.ShapeDtypeStruct((1, length), jnp.int32)).compile()
            self.hlo[fn.__name__] = program.as_text()
            self._prefill[length] = _Counted(program, self.notes, length)
        cache_s, _, _ = jax.eval_shape(
            lambda p, t: lm.prefill(p, mcfg, {"tokens": t}, None, max_len=max_len,
                                    routing=True),
            made, jax.ShapeDtypeStruct((1, self.buckets[0]), jnp.int32))

        def bench_decode(params, cache, tok):
            return lm.decode_step(params, mcfg, cache, tok, None, routing=True)

        program = jax.jit(bench_decode).lower(
            made, cache_s, jax.ShapeDtypeStruct((1, 1), jnp.int32)).compile()
        self.hlo["bench_decode"] = program.as_text()
        self._decode = _Counted(program, self.notes)
        self.compile_s = time.perf_counter() - t0

    def serve(self, seed: int, seconds: float, *, trace: bool = False,
              rate: Optional[float] = None) -> base.Window:
        self.notes.clear()
        return super().serve(seed, seconds, trace=trace, rate=rate)

    def routing(self, window: base.Window) -> Routing:
        """Means of the counters of the calls in the window (read now, in
        one transfer), summed over layers."""
        import jax

        notes = [n for n in self.notes if window.in_window(n[0])]
        self.notes.clear()
        if not notes:
            return Routing()
        counters = jax.device_get([c for _, _, c in notes])
        pre = [(length, c.sum(axis=0)) for (_, length, _), c in zip(notes, counters) if length]
        dec = [c.sum(axis=0) for (_, length, _), c in zip(notes, counters) if not length]
        r = {}
        if pre:
            r["prefill_pairs_per_token"] = (sum(float(c[0]) for _, c in pre)
                                            / sum(n for n, _ in pre))
            r["prefill_touched"] = float(np.mean([c[2] for _, c in pre]))
        if dec:
            r["decode_pairs"] = float(np.mean([c[0] for c in dec]))
            r["decode_touched"] = float(np.mean([c[2] for c in dec]))
        return Routing(**r)


def check(cfg: Dict[str, Any], seed: int, sample: List[base.Rec], max_prompt: int,
          max_out: int, control: bool = False
          ) -> Tuple[Dict[str, float], Optional[Dict[str, float]]]:
    """``lm_serve.check`` against ``qwen3_next.Reference``: over every served
    token of the sample, by how much its reference logit lies below the
    reference's best (mean and widest), and with ``control`` the same for
    the tokens the float8 pass puts first."""
    tokens = [np.array([int(np.asarray(t).reshape(())) for t in r.tokens], np.int64)
              for r in sample]
    positions = float(sum(len(t) for t in tokens))
    if any(((t < 0) | (t >= int(cfg["vocab_size"]))).any() for t in tokens):
        return {"positions": positions, "mean_logit_gap": math.inf,
                "max_logit_gap": math.inf}, None
    length = -(-(max_prompt + max_out) // 512) * 512
    n_rows = -(-max(len(t) for t in tokens) // 64) * 64
    seqs = [np.concatenate([r.prompt[0], t[:-1]]) for r, t in zip(sample, tokens)]
    rows = [ref.positions(r.prompt_len, len(t), n_rows) for r, t in zip(sample, tokens)]
    f32 = ref.Reference(cfg, seed)
    hid = f32.hidden(seqs, rows, length)
    if control:
        lo = ref.Reference(cfg, seed, mode="fp8")
        hid_lo = lo.hidden(seqs, rows, length)
    gaps, gaps_lo = [], []
    for i, t in enumerate(tokens):
        logits = f32.head(hid[i])
        gaps.append(ref.gaps(logits, t))
        if control:
            gaps_lo.append(ref.gaps(logits, ref.top_tokens(lo.head(hid_lo[i]), len(t))))
        del logits

    def numbers(g):
        g = np.concatenate(g)
        return {"positions": positions, "mean_logit_gap": float(g.mean()),
                "max_logit_gap": float(g.max())}

    return numbers(gaps), (numbers(gaps_lo) if control else None)


@dataclasses.dataclass
class Qwen3NextRun(base.LMRun):
    """What the per-layer readers read: ``lm_serve.LMRun`` with the routed
    shapes, and the device seconds under each named scope of the traced
    steps (``scope_s``)."""

    scope_s: Dict[str, float] = dataclasses.field(default_factory=dict)


def _scope_seconds(run: Qwen3NextRun, hlo: Dict[str, str]) -> Dict[str, float]:
    pairs = run.traced_steps()
    if not pairs:
        return {}
    tables = {name: op_scopes.scope_table(text) for name, text in hlo.items()}
    return op_scopes.scope_seconds(run.window.trace, tables, [span for _, span in pairs])


def run(ctx):
    """One run of a Qwen3-Next serving cell (see ``bench/run.py``)."""
    from bench.harness import Result

    cell = ctx.cell
    sc = Qwen3NextCell(cell)
    sc.compile()
    sc.load(ctx.seed)
    w = sc.serve(ctx.seed, ctx.seconds, trace=ctx.trace)
    backlog = cell.traffic["arrivals"] == "backlog"
    setup_s = w.t_open - ctx.t_process
    ctx.log(f"compiles inside the window: {w.compiles}; compile/load of programs "
            f"{sc.compile_s:.3f} s")
    memory_peak = ctx.memory_peak()
    routing = sc.routing(w)
    ctx.log(f"routing per call, summed over layers: {routing}")
    attempted, failed = base.attempted_failed(w, backlog)
    sample = sc.sample(w, ctx.seed)
    run_data = Qwen3NextRun(window=w, shapes=sc.shapes.with_routing(routing),
                            device_kind=ctx.device_kind,
                            summary=(trace_reduce.summarize(w.trace) if w.trace else None))
    run_data.scope_s = _scope_seconds(run_data, sc.hlo)
    if run_data.scope_s:
        ctx.log("device seconds by scope in the traced steps: "
                + ", ".join(f"{k} {v:.4f}" for k, v in sorted(run_data.scope_s.items())))
    buckets, max_out = sc.buckets, sc.max_out
    sc.free()
    del sc
    gc.collect()
    if not sample:
        checks = [("unfinished_sample", 1.0, 0.0)]
    else:
        t0 = time.perf_counter()
        got, _ = check(cell.config, ctx.seed, sample, max(buckets), max_out)
        ctx.log(f"check: {len(sample)} finished requests, {int(got['positions'])} "
                "served tokens compared with the float32 reference in "
                f"{time.perf_counter() - t0:.1f} s")
        checks = ctx.checks(got)
    return Result(attempted=attempted, failed=failed, setup_s=setup_s,
                  end_to_end=base.end_to_end(w), run=run_data, checks=checks,
                  memory_peak_bytes=memory_peak, summary=run_data.summary)


def control_rows(cell, seeds, seconds, control):
    """``bench/control.py``'s rows for this driver: per seed, the check's
    numbers and the control's, with the window's end-to-end numbers."""
    sc = Qwen3NextCell(cell)
    sc.compile()
    for seed in seeds:
        t0 = time.perf_counter()
        sc.load(seed)
        w = sc.serve(seed, seconds)
        sample = sc.sample(w, seed)
        e2e = base.end_to_end(w)
        routing = sc.routing(w)
        sc.free()
        t1 = time.perf_counter()
        row = {"seed": seed, "requests": len(sample), "end_to_end": e2e,
               "routing": dataclasses.asdict(routing)}
        if not sample:
            yield row, None, None
            continue
        got, lo = check(cell.config, seed, sample, max(sc.buckets), sc.max_out, control)
        row.update(got, serve_s=t1 - t0, check_s=time.perf_counter() - t1)
        if lo is not None:
            row.update({f"control_{k}": v for k, v in lo.items() if k != "positions"})
        yield row, got, lo


__all__ = ["Qwen3NextCell", "Qwen3NextRun", "check", "control_rows", "model_config",
           "program_tree", "run"]
