"""Flexible-SLA serving demo (the paper's core contribution, live).

Queries with Immediate / Relaxed / Best-of-Effort service levels hit the
REAL scheduling stack — pending queues -> relaxed/BoE schedulers ->
query coordinator over a PoolSpec registry — and execute real jitted
reduced models on thread-backed pools: a serialized cost-efficient
worker and an elastic task pool at 10x unit price.

The demo shows the stage-boundary machinery on live work:
  1. the admission-time price menu, quoted from the live registry;
  2. an IMMEDIATE arrival preempting a running BEST_EFFORT query at a
     decode-chunk boundary — the BoE query resumes from its checkpoint
     and re-runs nothing (its stage trace stays gap- and overlap-free).

    PYTHONPATH=src python examples/serve_sla.py
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.core.live import LiveConfig, LiveEngine, use_compile_cache
from repro.core.query import Query, QueryWork
from repro.core.sla import Policy, ServiceLevel, SLAConfig


def main():
    use_compile_cache()
    eng = LiveEngine(LiveConfig(
        policy=Policy.AUTO,
        cf_startup_s=0.2,
        sla=SLAConfig(relaxed_deadline_s=10.0, poll_period_s=0.05,
                      vm_overload_threshold=2, preempt_best_effort=True),
        decode_tokens=96, decode_chunk_tokens=2,
    ))

    print("price menu (quoted from the live pool registry):")
    for row in eng.price_menu(QueryWork(arch="paper-default")):
        print(f"  {row.sla:12s} pool={row.pool:4s}"
              f" pending<={row.est_pending_s:6.1f}s"
              f" est_cost={row.est_cost:.6f}")

    eng.models.ensure("paper-default", 1)  # warm jit outside the demo clock

    def submit(name, sla):
        q = Query(work=QueryWork(arch="paper-default", batch=1), sla=sla,
                  submit_time=0.0, source=name)
        eng.submit(q)
        return q

    qs = [submit("nightly report", ServiceLevel.BEST_EFFORT)]
    # let the BoE query get mid-plan, then hit it with an IMMEDIATE: it
    # is bumped at its next chunk boundary and the IMMEDIATE cuts in
    deadline = time.monotonic() + 60.0
    while not (0 < len(qs[0].stage_trace) < 40):
        if qs[0].state == "failed":
            raise SystemExit(f"BoE query failed: {qs[0].error}")
        if len(qs[0].stage_trace) >= 40 or qs[0].state == "done":
            break  # missed the window; proceed — drain still completes
        if time.monotonic() > deadline:
            break
        time.sleep(0.002)
    qs.append(submit("ad-hoc analysis", ServiceLevel.IMMEDIATE))
    qs.append(submit("dashboard refresh", ServiceLevel.RELAXED))
    time.sleep(0.2)
    qs.append(submit("dashboard refresh", ServiceLevel.RELAXED))
    qs.append(submit("ad-hoc analysis", ServiceLevel.IMMEDIATE))
    done = eng.drain(len(qs), timeout=300)

    print(f"\n{'query':20s} {'sla':4s} {'cluster':8s} {'pending':>8s}"
          f" {'exec':>7s} {'cost':>8s} {'stages':>6s} {'preempt':>7s}")
    total = {"vm": 0.0, "cf": 0.0}
    for q in sorted(done, key=lambda q: q.qid):
        total[q.cluster] += q.cost
        print(f"{q.source:20s} {q.sla.short:4s} {q.cluster:8s}"
              f" {q.pending_time:7.2f}s {q.exec_time:6.2f}s {q.cost:8.3f}"
              f" {len(q.stage_trace):6d} {q.preemptions:7d}")

    boe = next(q for q in done if q.sla is ServiceLevel.BEST_EFFORT)
    indices = sorted(e.index for e in boe.stage_trace)
    conserved = (
        indices == list(range(len(indices)))
        and abs(sum(e.chip_seconds for e in boe.stage_trace)
                - boe.chip_seconds) < 1e-9
    )
    print(f"\nBoE preempted {boe.preemptions}x at chunk boundaries;"
          f" resumed from checkpoint: {len(boe.stage_trace)} stages,"
          f" no re-run ({'exact' if conserved else 'MISMATCH'}:"
          f" sum(stage chip-s) == billed {boe.chip_seconds:.4f})")
    print(f"cost split: cost-efficient={total['vm']:.2f}"
          f" high-elastic={total['cf']:.2f}"
          f"  (elastic unit price is {eng.cfg.cf_price_multiplier}x)")
    compile_s = sum(eng.models.compile_s.values())
    print(f"jit compile warmed outside the billed window:"
          f" {compile_s:.2f}s never billed")


if __name__ == "__main__":
    main()
