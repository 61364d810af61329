"""Smoke test of the main path on a TPU: the MX trainer and the paged server.

    python3 chip_smoke.py             # one chip: train and serve phases
    python3 chip_smoke.py --chips 4   # four chips: the sharded trainer only

One chip:

* train: the ``repro.launch.train`` Trainer on olmo-paper full (8 layers,
  d_model 512, vocab 32000, context 512) at batch 16 x seq 512, a few
  steps under ``bf16`` and under ``mxfp8_e4m3``.  Losses are finite, the
  step-0 loss is near ln(vocab), the MX step-0 loss is within 1% of the
  bf16 one, and the compiled MX step calls every fused Pallas kernel of
  the training step (forward/dgrad/wgrad MX GEMMs, flash forward and
  backward) as ``tpu_custom_call``s rather than the jnp emulation.
* serve: a ``PagedServeEngine`` on the same model under ``e4m3_bf16act``
  answers greedy requests of mixed lengths, two waves sharing a prompt
  prefix, with the tokens of the slab ``ServeEngine``: the two agree
  until the first step where the best two logits are within one bf16 ulp
  of each other, and every token of each is greedy, within one bf16 ulp,
  under a separate batched prefill of its context.

Four chips: olmo-paper full under ``mxfp8_e4m3`` on an FSDP+TP mesh
(data=2, model=2) and on a pod mesh (pod=2, data=2) with the
MX-compressed cross-pod all-reduce, each against the one-chip loss
trajectory of the same steps, computed in this process on device 0.

Times printed are host-clock seconds on the chip named in the last line,
which is ``{"ok": true, "device": {...}}``.  Without a TPU, or run away
from the repository's ``src/``, it exits nonzero before printing any
result.  Everything runs in this one process: a child would find the chip
held.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH, VARIANT, BATCH, SEQ, STEPS = "olmo-paper", "full", 16, 512, 4
# Pallas kernels every quantized training step must call on the chip, as
# named in the op_name metadata of the compiled step's custom calls.
TRAIN_KERNELS = ("mx_matmul_pallas", "mx_matmul_dgrad_pallas",
                 "mx_matmul_wgrad_pallas", "mx_attn_fwd_pallas",
                 "mx_attn_bwd_pallas")
# Relative loss tolerances against the one-chip run: cross-device
# reduction order for FSDP+TP, plus bounded MX quantization noise on the
# compressed pod all-reduce (the gates of benchmarks/train_throughput.py).
SHARDED_TOL, POD_MX_TOL = 5e-3, 5e-2
# Greedy decoding is ill-posed where the best two logits tie.  The logits
# are bf16, so over a 32000-word vocabulary ties within one ulp are common,
# and the paged and slab paths (other shapes, other reduction orders) may
# break one differently.  A token counts as greedy when its logit is within
# one bf16 ulp of the best one (the ulp of that best logit).
BF16_MANT_BITS = 7


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAIL: {msg}")


def train(precision: str, *, variant: str = VARIANT, batch: int = BATCH,
          seq: int = SEQ, steps: int = STEPS, mesh: str | None = None,
          pod_compress: str | None = None) -> dict:
    """Build the launcher's Trainer, compile its step, run ``steps``."""
    import jax

    from repro.launch.train import build_trainer, parse_args

    argv = ["--arch", ARCH, "--variant", variant, "--precision", precision,
            "--batch", str(batch), "--seq", str(seq), "--steps", str(steps),
            "--log-every", "1"]
    if mesh:
        argv += ["--mesh", mesh]
    if pod_compress:
        argv += ["--pod-compress", pod_compress]
    trainer = build_trainer(parse_args(argv))
    t0 = time.perf_counter()
    text = trainer.lower_step().compile().as_text()
    compile_s = time.perf_counter() - t0
    hist = trainer.run(steps)
    run_start = trainer.events.of_kind("run_start")[0]
    return {
        "precision": precision, "mesh": mesh, "compile_s": compile_s,
        "loss": [h["loss"] for h in hist],
        "step_s": [h["time_s"] for h in hist],
        "fused_gemms": run_start["fused_gemms"],
        "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
        "missing_kernels": [k for k in TRAIN_KERNELS
                            if f"jit({k})/pallas_call" not in text],
        "param_devices": len(set().union(*(
            x.sharding.device_set for x in jax.tree.leaves(trainer.params)))),
    }


def serve(*, variant: str = VARIANT, max_len: int = SEQ, max_new: int = 16,
          seed: int = 0) -> dict:
    """Greedy requests through the paged and the slab engine, each token
    scored against a teacher-forced prefill of the context it was chosen
    in (see :func:`greedy_deficits`)."""
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import preset
    from repro.models import lm_init
    from repro.serve import PagedServeEngine, SamplingParams, ServeEngine

    cfg = get_config(ARCH, variant)
    qcfg = preset("e4m3_bf16act")
    params = lm_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.RandomState(seed)
    lens = [n * max_len // 512 for n in (37, 150, 300)]
    prefix = rng.randint(1, cfg.vocab, size=max_len // 4)
    waves = [[rng.randint(1, cfg.vocab, size=n) for n in lens]
             + [np.concatenate([prefix, rng.randint(1, cfg.vocab, size=20)])],
             [np.concatenate([prefix, rng.randint(1, cfg.vocab, size=45)])]]
    sp = SamplingParams(temperature=0.0, max_new_tokens=max_new)
    kw = dict(max_batch=4, max_len=max_len)
    engines = {
        "slab": ServeEngine(params, cfg, qcfg, bucket_prompts=False, **kw),
        "paged": PagedServeEngine(params, cfg, qcfg, page_size=32,
                                  n_pages=4 * max_len // 32, **kw),
    }
    out = {}
    for name, eng in engines.items():
        tokens, prompts, t0 = {}, {}, time.perf_counter()
        for wave in waves:
            for p in wave:
                prompts[eng.submit(p, sp)] = p
            tokens.update({r.rid: list(map(int, r.tokens))
                           for r in eng.drain()})
        out[name] = {"tokens": tokens, "wall_s": time.perf_counter() - t0,
                     "stats": eng.stats()}
        out[name].update(greedy_deficits(params, cfg, qcfg, prompts, tokens,
                                         max_len))
    return out


def greedy_deficits(params, cfg, qcfg, prompts: dict, tokens: dict,
                    max_len: int) -> dict:
    """For each generated token: how far its logit falls below the best
    one (``deficit``; 0 for the argmax), the gap between the best two
    logits (``margin``) and the bf16 ulp of the best one (``ulp``), under
    one batched prefill of the prompt plus the tokens before it — the
    engines' own path is not involved."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import lm_prefill

    rows, at, chosen, owner = [], [], [], []
    for rid, toks in tokens.items():
        ctx = np.concatenate([prompts[rid], toks[:-1]]).astype(np.int32)
        row = np.zeros(max_len, np.int32)
        row[:ctx.size] = ctx                 # causal: the pad is never seen
        for t, tok in enumerate(toks):
            rows.append(row)
            at.append(prompts[rid].size - 1 + t)
            chosen.append(tok)
            owner.append(rid)
    logits = jax.jit(lambda p, x, a: lm_prefill(p, x, cfg, qcfg, max_len,
                                                a)[0])(
        params, jnp.asarray(np.stack(rows)), jnp.asarray(at, jnp.int32))
    lg = np.asarray(logits, np.float32)
    top2 = np.sort(lg, axis=-1)[:, -2:]
    deficit = top2[:, 1] - lg[np.arange(len(chosen)), chosen]
    margin = top2[:, 1] - top2[:, 0]
    ulp = np.exp2(np.floor(np.log2(np.abs(top2[:, 1]))) - BF16_MANT_BITS)
    per = lambda v: {rid: v[np.asarray(owner) == rid] for rid in tokens}
    return {"deficit": per(deficit), "margin": per(margin), "ulp": per(ulp)}


def one_chip() -> None:
    from repro.configs import get_config

    runs = {p: train(p) for p in ("bf16", "mxfp8_e4m3")}
    for p, r in runs.items():
        print(f"[train] {p}: loss {r['loss']}  compile {r['compile_s']:.1f}s"
              f"  step {r['step_s'][1:]} s (chip)  tpu_custom_calls "
              f"{r['tpu_custom_calls']}  fused_gemms {r['fused_gemms']}",
              flush=True)
        require(all(math.isfinite(x) for x in r["loss"]),
                f"{p}: non-finite loss {r['loss']}")
        ln_v = math.log(get_config(ARCH, VARIANT).vocab)
        require(abs(r["loss"][0] - ln_v) < 0.1 * ln_v,
                f"{p}: step-0 loss {r['loss'][0]} is far from ln(vocab) "
                f"{ln_v:.4f}")
    bf, mx = runs["bf16"], runs["mxfp8_e4m3"]
    require(abs(mx["loss"][0] - bf["loss"][0]) < 0.01 * bf["loss"][0],
            f"MX step-0 loss {mx['loss'][0]} vs bf16 {bf['loss'][0]}")
    require(mx["fused_gemms"], "the trainer did not dispatch fused kernels")
    require(mx["tpu_custom_calls"] > 0 and not mx["missing_kernels"],
            f"compiled MX step lacks kernels {mx['missing_kernels']} "
            f"({mx['tpu_custom_calls']} tpu_custom_calls)")

    res = serve()
    slab, paged = res["slab"]["tokens"], res["paged"]["tokens"]
    for name, r in res.items():
        s = r["stats"]
        worst = max(float(d.max()) for d in r["deficit"].values())
        print(f"[serve] {name}: {len(r['tokens'])} requests, "
              f"{s['decode_tokens']:.0f} decode tokens, wall "
              f"{r['wall_s']:.1f}s (chip, compiles included), max greedy "
              f"deficit {worst}"
              + (f", prefix hits {s['prefix_hits']:.0f}"
                 if "prefix_hits" in s else ""), flush=True)
        for rid, d in r["deficit"].items():
            require(bool((d <= r["ulp"][rid]).all()),
                    f"{name} request {rid}: token logits {d.tolist()} below "
                    f"the best one by more than one bf16 ulp "
                    f"{r['ulp'][rid].tolist()}")
    require(len(slab) >= 4 and slab.keys() == paged.keys(),
            f"requests answered: slab {sorted(slab)}, paged {sorted(paged)}")
    same = 0
    for rid in slab:
        n = next((i for i, (a, b) in enumerate(zip(slab[rid], paged[rid]))
                  if a != b), None)
        same += n is None
        if n is not None:
            # A split is only legitimate where the context both engines
            # shared ended in a tie (then each was checked greedy above).
            margin = float(res["slab"]["margin"][rid][n])
            ulp = float(res["slab"]["ulp"][rid][n])
            print(f"[serve] request {rid}: paged and slab split at token "
                  f"{n}, where the best two logits are {margin} apart "
                  f"(one bf16 ulp is {ulp})", flush=True)
            require(margin <= ulp, f"request {rid}: paged {paged[rid]} != "
                    f"slab {slab[rid]} at token {n} (margin {margin})")
    print(f"[serve] paged == slab tokens on {same}/{len(slab)} requests",
          flush=True)
    require(res["paged"]["stats"]["prefix_hits"] > 0,
            "the second wave did not share the cached prefix pages")


def four_chips() -> None:
    import jax
    require(len(jax.devices()) >= 4, f"{len(jax.devices())} chips, not 4")
    ref = train("mxfp8_e4m3")
    runs = {"fsdp_tp": (train("mxfp8_e4m3", mesh="2,2"), SHARDED_TOL),
            "pod_mx": (train("mxfp8_e4m3", mesh="2,1,2",
                             pod_compress="e4m3"), POD_MX_TOL)}
    print(f"[4chip] one chip (device 0): loss {ref['loss']}  step "
          f"{ref['step_s'][1:]} s (chip)", flush=True)
    for name, (r, tol) in runs.items():
        gap = max(abs(a - b) / abs(b) for a, b in zip(r["loss"], ref["loss"]))
        print(f"[4chip] {name} mesh {r['mesh']}: loss {r['loss']}  max rel "
              f"gap {gap:.3e} (tol {tol})  params on {r['param_devices']} "
              f"devices  compile {r['compile_s']:.1f}s  step "
              f"{r['step_s'][1:]} s (chip)", flush=True)
        require(all(math.isfinite(x) for x in r["loss"]),
                f"{name}: non-finite loss")
        require(gap < tol, f"{name}: loss gap {gap} >= {tol}")
        require(r["param_devices"] == 4,
                f"{name}: params on {r['param_devices']} devices, not 4")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded trainer phase")
    args = ap.parse_args(argv)
    require((SRC / "repro").is_dir(), f"no repro package under {SRC}")
    sys.path.insert(0, str(SRC))
    import jax

    import repro
    from repro.core import fused_gemms_enabled

    require(Path(repro.__file__).resolve().is_relative_to(SRC),
            f"imported repro from {repro.__file__}, not {SRC}")
    require(jax.default_backend() == "tpu",
            f"no TPU: JAX backend is {jax.default_backend()!r}")
    require(fused_gemms_enabled(),
            "REPRO_FUSED_GEMM disables the fused Pallas kernels")
    four_chips() if args.chips == 4 else one_chip()
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
