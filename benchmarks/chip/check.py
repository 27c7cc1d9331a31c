"""What decides ``correct``: served tokens against the plain reference.

Every request that finished in the window is in the sample: a share of
its steps, drawn from the run's seed, is replayed through the float32
reference on the canvas that step saw — the prompt, the tokens
committed at earlier steps, and [MASK] everywhere else — and the
reference's logits are read at the positions the step committed.  For
each served token the number read is its gap: how far its reference
logit lies below the reference's best.  ``gap_max`` is the widest gap,
``gap_mean`` the mean over every token checked.

Bookkeeping is exact: every generated position committed once, the
harvested output equal to those commits, and no request left without
a commit two steps after its admission.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

import reference
import cell
from cell import HERE, Record, WindowResult, load_json, row_len


def load_limits(workload: str, root: str = HERE) -> Dict[str, Any]:
    return load_json(root, "checks", f"{workload}.json")


def bookkeeping_faults(rec: Record, mask_id: int, vocab: int) -> int:
    """0 when the request's commits and its output agree."""
    seen = np.full(rec.req.gen_len, -1, np.int64)
    faults = 0
    for _, positions, toks in rec.commits:
        for off, t in zip(positions, toks):
            if not 0 <= off < rec.req.gen_len or seen[off] >= 0:
                faults += 1
                continue
            seen[off] = t
    out = np.asarray(rec.output if rec.output is not None else (), np.int64)
    if (out.shape != seen.shape or not np.array_equal(out, seen)
            or np.any((out < 0) | (out >= vocab) | (out == mask_id))):
        faults += 1
    return faults


def stalled(win: WindowResult) -> int:
    """Requests admitted in the window that saw two steps and committed
    nothing: every step of the serving schedulers commits a token in
    each live row."""
    n = 0
    for rec in win.records:
        t_adm = win.admitted.get(rec.uid)
        if t_adm is None or rec.commits:
            continue
        if sum(1 for t in win.step_times if t > t_adm) >= 2:
            n += 1
    return n


def sample_steps(recs: List[Record], canvases: int, seed: int
                 ) -> List[List[int]]:
    """For each record, the sorted steps replayed: an equal share of
    ``canvases`` (at least one step each), drawn from ``seed``."""
    rng = np.random.default_rng([int(seed), 13])
    share = max(1, canvases // max(1, len(recs)))
    out = []
    for rec in recs:
        n = len(rec.commits)
        out.append(sorted(rng.choice(n, min(share, n), replace=False)
                          .tolist()))
    return out


def replay(recs: List[Record], steps: List[List[int]],
           mix: Dict[str, Any], mask_id: int):
    """(canvases [C, N], kv_len [C], positions [C, M], tokens [C, M],
    valid [C, M]): one canvas per replayed step, as the step saw it,
    with the positions it committed and their tokens.  N is the mix's
    canvas and M the most tokens one step committed."""
    n = mix["canvas"]
    c = sum(len(s) for s in steps)
    m = max((len(rec.commits[j][1]) for rec, s in zip(recs, steps)
             for j in s), default=1)
    canvases = np.full((c, n), mask_id, np.int32)
    kv = np.zeros(c, np.int32)
    pos = np.zeros((c, m), np.int32)
    toks = np.zeros((c, m), np.int64)
    valid = np.zeros((c, m), bool)
    i = 0
    for rec, chosen in zip(recs, steps):
        plen = len(rec.req.prompt)
        canvas = np.full(n, mask_id, np.int32)
        canvas[:plen] = rec.req.prompt
        want = set(chosen)
        for step, (_, positions, step_toks) in enumerate(rec.commits):
            if step in want:
                canvases[i] = canvas
                kv[i] = row_len(mix, plen, rec.req.gen_len)
                for j, (off, t) in enumerate(zip(positions, step_toks)):
                    pos[i, j], toks[i, j], valid[i, j] = plen + off, t, True
                i += 1
            for off, t in zip(positions, step_toks):
                canvas[plen + off] = t
    return canvases, kv, pos, toks, valid


def gap_readings(family, cfg: Dict[str, Any], params, recs: List[Record],
                 mix: Dict[str, Any], steps: List[List[int]],
                 control: bool = False) -> np.ndarray:
    """The gaps of every token the replayed steps committed, against the
    reference of the configuration's ``family``.  With ``control`` they
    are the gaps of the token the int8 control puts first at the same
    positions."""
    mask_id = cell.mask_id(cfg)
    canvases, kv, pos, toks, valid = replay(recs, steps, mix, mask_id)
    if not len(canvases):
        return np.zeros(0)
    flat_toks = toks.reshape(-1)
    if control:
        low = reference.hidden_at(family, cfg, params, canvases, kv, pos,
                                  int8=True)
        _, flat_toks = reference.gaps_at(
            cfg, params, low.reshape(-1, low.shape[-1]), flat_toks,
            int8=True)
        del low
    rows = reference.hidden_at(family, cfg, params, canvases, kv, pos)
    gaps, _ = reference.gaps_at(cfg, params, rows.reshape(-1, rows.shape[-1]),
                                flat_toks)
    return gaps[valid.reshape(-1)]


def gap_numbers(gaps: np.ndarray) -> Dict[str, float]:
    out = {"tokens_checked": float(len(gaps))}
    if len(gaps):
        out["gap_max"] = float(gaps.max())
        out["gap_mean"] = float(gaps.mean())
    return out


def numbers(family, cfg: Dict[str, Any], params, win: WindowResult,
            mix: Dict[str, Any], limits: Dict[str, Any], seed: int
            ) -> Dict[str, float]:
    """Every number the cell's limits are set on."""
    mask_id, vocab = cell.mask_id(cfg), cfg["vocab_size"]
    done = [r for r in win.records if r.output is not None]
    out = {
        "finished": float(len(done)),
        "bookkeeping_faults": float(sum(bookkeeping_faults(r, mask_id, vocab)
                                        for r in done)),
        "stalled_requests": float(stalled(win)),
    }
    steps = sample_steps(done, limits["canvases"], seed)
    out.update(gap_numbers(gap_readings(family, cfg, params, done, mix,
                                        steps)))
    return out


def judge(nums: Dict[str, float], limits: Dict[str, Any]
          ) -> Tuple[bool, List[Tuple[str, float, str]]]:
    """(correct, [(name, number, limit text)]).  A missing number —
    nothing finished — is not correct."""
    rows, ok = [], True
    for name, lim in limits["limits"].items():
        val = nums.get(name)
        if "max" in lim:
            good = val is not None and val <= lim["max"]
            text = f"<= {lim['max']}"
        else:
            good = val is not None and val >= lim["min"]
            text = f">= {lim['min']}"
        ok = ok and good
        rows.append((name, float("nan") if val is None else val, text))
    return ok, rows
