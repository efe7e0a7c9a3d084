"""One-chip bring-up smoke: qwen2-0.5b at its published widths in bf16,
served through LiveEngine with the Pallas attention kernels.

    python chip_smoke.py

It needs a TPU. With none (or outside a checkout of this repository) it
exits non-zero and prints no result. It runs in one process and starts
no child, so it is the only user of the chip. Phases, in order; any
failure raises, and the script exits non-zero:

  device     platform, device kind and count, as JAX reports them
  build      LiveEngine compiles and warms prefill + decode at batch 1
             and 4, outside the billed window; compile seconds
  kernels    every compiled served program holds the Pallas kernels
             (tpu_custom_call): no attention call fell back to jnp
  reference  served bf16 prefill logits against a plain float32 jnp
             forward on the same parameters, matmul precision "highest"
  serve      IMMEDIATE, RELAXED and BEST_EFFORT queries at batch 1 and
             4, one BEST_EFFORT query preempted by an IMMEDIATE one;
             every query done, every stage trace complete, stitched in
             time and summing to the billed chip-seconds

The last line of stdout is the JSON verdict. Parameters and prompts come
from seeds. The compile cache is JAX_COMPILATION_CACHE_DIR when that is
set, else the checkout's .jax_cache directory.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.live import LiveConfig, LiveEngine, use_compile_cache  # noqa: E402
from repro.core.pools import PoolSpec  # noqa: E402
from repro.core.query import Query, QueryWork  # noqa: E402
from repro.core.sla import ServiceLevel, SLAConfig  # noqa: E402
from repro.models.transformer import LM  # noqa: E402

ARCH = "qwen2-0.5b"
BATCHES = (1, 4)
#: a multiple of 128, so prefill runs the flash kernel's 128-row blocks
PROMPT_TOKENS = 128
DECODE_TOKENS = 64
DECODE_CHUNK_TOKENS = 4
#: bf16 keeps 8 mantissa bits (relative rounding 2^-9 per op); over 24
#: residual layers that compounds to about 1-2% of a logit's scale, so
#: the served logits may differ from the float32 reference by at most
#: 5% of the largest reference logit
LOGIT_TOL_FRAC = 0.05


def live_config(published_widths: bool = True) -> LiveConfig:
    """One reserved chip that lets an IMMEDIATE query preempt a running
    BEST_EFFORT one at a decode-chunk boundary."""
    return LiveConfig(
        pools=[PoolSpec(name="vm", kind="reserved", chips=1)],
        sla=SLAConfig(relaxed_deadline_s=5.0, poll_period_s=0.01,
                      vm_overload_threshold=1_000,
                      preempt_best_effort=True),
        prompt_tokens=PROMPT_TOKENS,
        decode_tokens=DECODE_TOKENS,
        decode_chunk_tokens=DECODE_CHUNK_TOKENS,
        published_widths=published_widths,
        impl="pallas",
    )


def phase_build(eng: LiveEngine, arch: str = ARCH) -> dict:
    for b in BATCHES:
        eng.models.ensure(arch, b)
    return {f"batch{b}": eng.models.compile_s[(arch, b)] for b in BATCHES}


def phase_kernels(eng: LiveEngine, arch: str = ARCH) -> None:
    for b in BATCHES:
        lm = eng.models.ensure(arch, b)
        for name, exe in (("prefill", lm.prefill), ("decode", lm.decode)):
            if "tpu_custom_call" not in exe.as_text():
                raise AssertionError(
                    f"compiled {name} at batch {b} holds no Pallas kernel"
                )


def phase_reference(eng: LiveEngine, arch: str = ARCH, seed: int = 0):
    """Max |served - reference| over the last-position logits, and the
    tolerance it is held to."""
    lm = eng.models.ensure(arch, 1)
    toks = jax.random.randint(
        jax.random.PRNGKey(seed), (1, PROMPT_TOKENS), 0, lm.cfg.vocab_size
    )
    _, _, served = lm.prefill(lm.params, toks, {})
    ref_model = LM(lm.cfg, impl="jnp")
    params32 = jax.tree.map(lambda p: p.astype(jnp.float32), lm.params)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(
            lambda p, t: ref_model.forward(p, t, dtype=jnp.float32)[0][:, -1]
        )(params32, toks)
    if served.shape != ref.shape or not bool(jnp.all(jnp.isfinite(served))):
        raise AssertionError(
            f"served logits {served.shape} not finite or not {ref.shape}"
        )
    err = float(jnp.max(jnp.abs(served - ref)))
    tol = LOGIT_TOL_FRAC * float(jnp.max(jnp.abs(ref)))
    if not err <= tol:
        raise AssertionError(f"prefill logits max error {err} > {tol}")
    same_argmax = bool(jnp.argmax(served) == jnp.argmax(ref))
    return err, tol, same_argmax


def _check_trace(q: Query, n_stages: int) -> None:
    tr = q.stage_trace
    if [e.index for e in tr] != list(range(n_stages)):
        raise AssertionError(f"Q{q.qid} ran stages {[e.index for e in tr]}")
    for a, b in zip(tr, tr[1:]):
        if b.start < a.finish:
            raise AssertionError(f"Q{q.qid} stage {b.index} overlaps")
    trace_cs = sum(e.chip_seconds for e in tr)
    trace_cost = sum(e.cost for e in tr)
    if abs(trace_cs - q.chip_seconds) > 1e-9 * max(1.0, q.chip_seconds):
        raise AssertionError(
            f"Q{q.qid} billed {q.chip_seconds} chip-s, trace sums {trace_cs}"
        )
    if abs(trace_cost - q.cost) > 1e-9 * max(1.0, q.cost):
        raise AssertionError(f"Q{q.qid} cost {q.cost}, trace sums {trace_cost}")


def phase_serve(eng: LiveEngine, arch: str = ARCH,
                timeout_s: float = 600.0) -> list[Query]:
    """Serve all three levels at both batch sizes; an IMMEDIATE arrival
    preempts the batch-1 BEST_EFFORT query mid-plan. Drains the engine."""
    n_stages = 1 + -(-DECODE_TOKENS // DECODE_CHUNK_TOKENS)

    def submit(sla: ServiceLevel, batch: int) -> Query:
        q = Query(work=QueryWork(arch=arch, batch=batch), sla=sla,
                  submit_time=0.0, source=f"{sla.short}-b{batch}")
        eng.submit(q)
        return q

    boe = submit(ServiceLevel.BEST_EFFORT, 1)
    deadline = time.monotonic() + timeout_s
    while not 0 < len(boe.stage_trace) < n_stages - 2:
        if boe.state in ("done", "failed") or time.monotonic() > deadline:
            raise AssertionError(
                f"BEST_EFFORT query never seen mid-plan ({boe.state})"
            )
        time.sleep(0.0005)
    qs = [boe, submit(ServiceLevel.IMMEDIATE, 1),
          submit(ServiceLevel.RELAXED, 1)]
    qs += [submit(sla, 4) for sla in ServiceLevel]
    out = eng.drain(len(qs), timeout=timeout_s)
    bad = [f"Q{q.qid} {q.source} {q.state} {q.error}"
           for q in qs if q.state != "done"]
    if len(out) != len(qs) or bad:
        raise AssertionError(f"{len(out)}/{len(qs)} drained; not done: {bad}")
    if boe.preemptions < 1:
        raise AssertionError("the BEST_EFFORT query was never preempted")
    for q in qs:
        _check_trace(q, n_stages)
    return qs


def main() -> int:
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU visible ({device}); nothing was run",
              file=sys.stderr)
        return 1
    print(f"device: {device}", flush=True)
    print(f"compile cache: {use_compile_cache()}", flush=True)

    eng = LiveEngine(live_config())
    try:
        compile_s = phase_build(eng)
        lm = eng.models.ensure(ARCH, 1)
        c = lm.cfg
        n_params = sum(p.size for p in jax.tree.leaves(lm.params))
        print(f"model: {c.name} layers={c.num_layers} d_model={c.d_model}"
              f" heads={c.num_heads}/{c.num_kv_heads} head_dim={c.head_dim}"
              f" d_ff={c.d_ff} vocab={c.vocab_size} params={n_params}"
              f" dtype={eng.models.dtype.__name__} impl={eng.models.impl}"
              f" kv_len={eng.models.kv_len}", flush=True)
        print(f"build: compile+warm seconds {compile_s}", flush=True)
        phase_kernels(eng)
        print("kernels: tpu_custom_call in prefill and decode at batch"
              f" {list(BATCHES)}", flush=True)
        err, tol, same_argmax = phase_reference(eng)
        print(f"reference: prefill logits max|bf16 - f32| = {err}"
              f" <= tol {tol} ({LOGIT_TOL_FRAC} x max|ref|);"
              f" same argmax: {same_argmax}", flush=True)
        t0 = time.monotonic()
        qs = phase_serve(eng)
        wall = time.monotonic() - t0
    finally:
        eng.shutdown()
    for q in qs:
        print(f"serve: {q.source:7s} done stages={len(q.stage_trace)}"
              f" preemptions={q.preemptions} latency_s={q.latency}"
              f" billed_chip_s={q.chip_seconds}", flush=True)
    print(f"serve: {len(qs)} queries done in {wall} s host wall,"
          " traces conserved", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
