"""How ``correct`` is decided.

Each number compared is printed beside its limit. The run is correct
when every number is at most its limit.

* ``unaccounted``: window queries not reported finished exactly once
  (failed, never finished within a minute of the close, or reported
  twice). Limit 0.
* ``trace_faults``: executed programs whose stage trace is not the full
  plan in order, overlaps itself, or does not sum to what its queries
  were billed. Limit 0.
* ``kernel_missing``: served programs (prefill and decode, at every
  warmed batch size) with no Pallas kernel in them. Limit 0.
* ``tokens_missing``: sampled rows that served fewer tokens than the
  plan asks. Limit 0.
* ``logit_gap``: over a sample of finished rows drawn from the seed, the
  widest gap by which a served (greedy) token's logit lies below the
  float32 reference's best logit at that position. The reference is run
  once per row over the prompt and the served tokens. Its limit is in
  the configuration file, with the readings it was set from.
* ``metrics_missing`` (added by ``report.result``): metrics the cell
  names that read nothing in the run. Limit 0.

The control (``against_reference(..., quant="fp8")``) puts the reference
computed one precision below the served one in the program's place: its
first choice at each position of the same rows stands for the served
token, and the same comparison has to find it not correct.
"""
from __future__ import annotations

import math

import numpy as np


def _n_stages(run) -> int:
    chunk = int(run.config["deployment"]["decode_chunk_tokens"])
    return 1 + -(-int(run.traffic["output_tokens"]) // chunk)


def structural(run, qs, eng, require_chip: bool) -> dict:
    unaccounted = sum(
        1 for r in run.queries if r.state != "done" or r.done_count != 1
    )
    qmap = {q.qid: q for q in qs}
    n = _n_stages(run)
    faults = 0
    for ex in run.executions.values():
        members = [qmap[m] for m in ex.members if m in qmap]
        if not members or members[0].state != "done":
            continue
        t = members[0].stage_trace
        billed = sum(m.chip_seconds for m in members)
        traced = sum(e.chip_seconds for e in t)
        if ([e.index for e in t] != list(range(n))
                or any(b.start < a.finish for a, b in zip(t, t[1:]))
                or abs(traced - billed) > 1e-9 * max(1.0, billed)):
            faults += 1
    checks = {
        "unaccounted": {"value": unaccounted, "limit": 0},
        "trace_faults": {"value": faults, "limit": 0},
    }
    if require_chip:
        missing = 0
        for (arch, b), lm in list(eng.models._models.items()):
            for exe in (lm.prefill, lm.decode):
                text = exe.as_text() if hasattr(exe, "as_text") else ""
                if "tpu_custom_call" not in text:
                    missing += 1
        checks["kernel_missing"] = {"value": missing, "limit": 0}
    return checks


def sample_rows(run, seed: int) -> list:
    """(execution qid, row, member qid) of the rows to check, drawn from
    the seed among finished window queries: half from programs of more
    than one row (fused queries, or a query's own batch), half from
    programs of one, and always the last row of the largest batch, the
    row that a batch cut short or mixed up gets wrong. Each member holds
    the traffic's ``batch`` rows, in member order."""
    done = {r.qid for r in run.queries if r.state == "done"}
    b = int(run.traffic["batch"])
    fused, alone = [], []
    for ex in run.executions.values():
        rows = [(ex.qid, j * b + i, m) for j, m in enumerate(ex.members)
                for i in range(b) if m in done]
        (fused if len(ex.members) * b > 1 else alone).extend(rows)
    want = int(run.traffic["check_rows"])
    rng = np.random.default_rng([int(seed) % (1 << 63), 11])
    picked = []
    if fused:
        largest = max(run.executions.values(), key=lambda e: len(e.members))
        picked.append([r for r in fused if r[0] == largest.qid][-1])
    for pool, k in ((fused, want // 2 if alone else want), (alone, want)):
        rest = [r for r in pool if r not in picked]
        k = max(0, min(len(rest), k - (len(picked) if pool is fused else 0)))
        idx = rng.choice(len(rest), size=k, replace=False) if k else []
        picked += [rest[i] for i in sorted(idx)]
        if len(picked) >= want:
            break
    return picked[:want]


def row_tokens(ex, j: int):
    """(prompt row, served tokens) of row j of an execution, on the host."""
    import jax

    prompt = np.asarray(jax.device_get(ex.prompt))[j]
    toks = [int(np.asarray(jax.device_get(t))[j, 0]) for t in ex.tokens]
    return prompt, np.asarray(toks, np.int64)


def widest_gap(ref_logits: np.ndarray, tokens: np.ndarray) -> float:
    """Largest (best reference logit - reference logit of the token)."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return float(np.max(best - got))


def against_reference(run, family, seed: int, rows: list,
                      quant: str | None = None) -> dict:
    """``tokens_missing`` and ``logit_gap`` of the sampled rows. With
    ``quant``, the control: the reference in that precision stands in for
    the program, its first choice at each position for the served token."""
    want = int(run.traffic["output_tokens"]) + 1
    S = run.prompt_tokens
    weights = family.make_weights(run.config, seed)
    gap, short = 0.0, 0
    for _, (prompt, served) in rows:
        if len(served) < want:
            short += 1
            continue
        seq = np.concatenate([prompt, served[:-1]])
        ref = np.asarray(family.reference_logits(run.config, weights, seq, S - 1))
        if quant is not None:
            served = np.asarray(family.reference_logits(
                run.config, weights, seq, S - 1, quant=quant)).argmax(-1)
        gap = max(gap, widest_gap(ref, served))
    return {
        "tokens_missing": {"value": short, "limit": 0},
        "logit_gap": {"value": gap if rows else math.inf,
                      "limit": float(run.config["check"]["logit_gap_limit"])},
    }


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
