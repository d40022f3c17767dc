"""The comparison that decides ``correct``.

Once the window has closed, two numbers are compared with the plain
reference, which programs and calibrates its own arrays from the
benchmark's weights, key and calibration tokens.

* ``first_layer_v_off_share``: the first layer's attention values in
  the KV cache of every slot still serving when the window closed,
  written by the timed prefill and decode programs, against the
  reference's values for the same tokens.  Nothing before the first
  layer's sites depends on other positions or layers, so a run that
  computes what the configuration states puts only the rare value that
  lies on an ADC or input-quantizer decision on the other side of it; a
  product or a conductance in lower precision moves many values by a
  code.  The share of values half a code or more from the reference's
  is compared.
* ``logit_gap_mean_codes``: a sample of the finished requests, drawn
  from the seed and always holding the one that served the most tokens,
  is run through the reference teacher-forced over its prompt and served
  tokens.  At each served position the gap is how far the served token's
  reference logit lies below the reference's best, in codes of the
  reference head's ADC; the mean over the sample is compared.  Past the
  first layer a value moved across one decision moves the next layer's
  inputs, so sound runs serve some tokens off the reference's best; the
  mean still catches a token altered where it is produced, or a head or
  attention that computes something else.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import sys

import numpy as np

from chipbench.traffic import seed_sequence


def sample(finished: List[Dict[str, Any]], seed: int,
           min_tokens: int, max_requests: int) -> List[Dict[str, Any]]:
    """The longest finished request, then others in an order drawn from
    the seed, until ``min_tokens`` served tokens or ``max_requests``."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: (-len(finished[i]["tokens"]),
                                  -len(finished[i]["prompt"]), i))
    first, rest = order[0], order[1:]
    rng = np.random.default_rng(seed_sequence(seed, 4))
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, served = [], 0
    for i in [first] + rest:
        if len(out) >= max_requests or served >= min_tokens:
            break
        out.append(finished[i])
        served += len(finished[i]["tokens"])
    return out


def teacher_forced(reqs: List[Dict[str, Any]]) -> Tuple[list, list]:
    """(sequence, first compared position) of each request: the prompt
    and every served token but the last; position ``len(prompt) - 1``
    predicts the first served token."""
    seqs, starts = [], []
    for r in reqs:
        toks = np.asarray(r["tokens"], np.int32)
        seqs.append(np.concatenate([np.asarray(r["prompt"], np.int32),
                                    toks[:-1]]))
        starts.append(len(r["prompt"]) - 1)
    return seqs, starts


def gaps(ref_logits: List[np.ndarray], chosen: List[np.ndarray],
         code: float) -> np.ndarray:
    """``max(ref) - ref[chosen]`` at every position, in codes."""
    out = []
    for lg, tok in zip(ref_logits, chosen):
        tok = np.asarray(tok, np.int64)
        got = np.take_along_axis(lg, tok[:, None], axis=1)[:, 0]
        out.append(lg.max(axis=1) - got)
    return np.concatenate(out) / code


def shapes(traffic: Dict[str, Any]) -> Dict[str, int]:
    """One padded sequence length and one count of compared rows per
    cell, so that the reference compiles once per cell."""
    from chipbench.traffic import size_bounds

    up = lambda n: -(-n // 128) * 128
    return {"length": up(int(traffic["max_len"])),
            "rows": up(int(size_bounds(traffic["output_tokens"])[1]))}


def served_ok(r: Dict[str, Any], vocab: int) -> bool:
    toks = np.asarray(r["tokens"])
    return (toks.size == r["max_new"] and bool(((toks >= 0)
                                                & (toks < vocab)).all()))


def value_gaps(cached: List[Dict[str, Any]], ref: List[np.ndarray],
               code: float) -> np.ndarray:
    """``|cached - reference|`` of every first-layer value, in codes."""
    return np.concatenate([np.abs(r["v"] - v).reshape(-1)
                           for r, v in zip(cached, ref)]) / code


def first_layer_off_share(run, server, reference,
                          precision: str = "highest") -> float:
    """Share of the first layer's cached values, of every slot live when
    the window closed, that lie half an ADC code or more from the
    reference's."""
    ref, code = reference.first_layer_values(
        run.config, server.params, server.k_prog, server.calib,
        [r["seq"] for r in run.cached], precision=precision,
        length=shapes(run.cell.traffic)["length"], batch=run.slots)
    g = value_gaps(run.cached, ref, code)
    print(f"chipbench: first-layer values off the reference by half a "
          f"code or more: {int((g >= 0.5).sum())} of {g.size}, widest "
          f"{float(g.max())!r} codes", file=sys.stderr)
    return float((g >= 0.5).mean())


def compared_requests(run) -> Tuple[int, List[Dict[str, Any]]]:
    """How many finished requests were served wrong (count or ids), and
    the sample of the others that is compared with the reference."""
    tf, vocab = run.cell.traffic, int(run.config["vocab_size"])
    finished = [dict(prompt=r.prompt, tokens=r.tokens, max_new=r.max_new)
                for r in run.reqs.values() if r.tokens is not None]
    bad = sum(not served_ok(r, vocab) for r in finished)
    pick = sample([r for r in finished if served_ok(r, vocab)], run.seed,
                  int(tf["check"]["min_served_tokens"]),
                  int(tf["check"]["max_requests"]))
    return bad, pick


def judge(run, server, limits: Dict[str, float], reference):
    """Every number compared, with its limit (a check passes when its
    value is at most its limit), and the served tokens compared."""
    c, tf = run.config, run.cell.traffic
    bad, pick = compared_requests(run)
    checks = {
        "bad_requests": {"value": bad, "limit": 0},
        "site_classes_missing": {
            "value": len(server.expected - server.built), "limit": 0},
        "no_request_compared": {"value": int(not pick), "limit": 0},
    }
    if pick:
        seqs, starts = teacher_forced(pick)
        lg, code = reference.logits(c, server.params, server.k_prog,
                                    server.calib, seqs, starts,
                                    **shapes(tf))
        g = gaps(lg, [r["tokens"] for r in pick], code)
        checks["logit_gap_mean_codes"] = {
            "value": float(g.mean()), "limit": limits["logit_gap_mean_codes"]}
        print(f"chipbench: served tokens off the reference's best: "
              f"{int((g > 0).sum())} of {g.size}, widest gap "
              f"{float(g.max())!r} codes", file=sys.stderr)
    if run.cached:
        share = first_layer_off_share(run, server, reference)
        checks["first_layer_v_off_share"] = {
            "value": share, "limit": limits["first_layer_v_off_share"]}
    for v in checks.values():
        v["ok"] = bool(v["value"] <= v["limit"])
    compared = sum(len(r["tokens"]) for r in pick)
    return checks, compared
