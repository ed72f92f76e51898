"""The comparison that decides ``correct``.

1. Served tokens against the plain reference.  Once the window has closed
   and the served model is freed, a sample of the finished requests,
   drawn from the seed, is run through the float32 reference over its
   prompt and its served tokens.  The sample holds the request with the
   most served tokens, one whose prefix was adopted from the prefix cache
   and one STRICT one where the run has them, then seeded others until
   it holds ``MIN_TOKENS`` served tokens.  The number compared is the
   widest gap by which a served token's reference logit lies below the
   reference's best at its position (``max_logit_gap``).
2. The STRICT guarantee, as the operation log shows it: replaying the log
   gives back exactly the committed extents of every live STRICT
   sequence and nothing of a finished one, and the log holds one page
   publish per full page each STRICT request filled
   (``strict_log_mismatch``, exact: limit 0).

The control (``--control 1``) also reads, at the same positions, the gap
of the token that the float8 reference puts first (``control_logit_gap``);
the harness then compares that gap against the limit in place of the
served tokens' own, as if the float8 reference had served them.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

MIN_TOKENS = 256
MAX_REQUESTS = 24


def sample(reqs: List, seed: int) -> List:
    """The requests to compare, as described above.  ``reqs`` are the
    program's finished, untruncated Request objects."""
    pool = sorted((r for r in reqs if r.output), key=lambda r: r.rid)
    if not pool:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 99]))
    chosen = [max(pool, key=lambda r: (len(r.output), -r.rid))]
    for want in (lambda r: r.prefix_tokens > 0,
                 lambda r: r.mode.name == "STRICT"):
        if not any(want(r) for r in chosen):
            cands = [r for r in pool if want(r) and r not in chosen]
            if cands:
                chosen.append(cands[rng.integers(len(cands))])
    rest = [r for r in pool if r not in chosen]
    for i in rng.permutation(len(rest)):
        if sum(len(r.output) for r in chosen) >= MIN_TOKENS or \
                len(chosen) >= MAX_REQUESTS:
            break
        chosen.append(rest[i])
    return chosen


def logit_gaps(reference, reqs: List, control: bool) -> Dict[str, float]:
    """The widest gaps over the sample, and how much was compared."""
    worst = ctrl_worst = 0.0
    n_tok = 0
    for r in reqs:
        g, c = reference.gaps(r.prompt, r.output, control=control)
        worst = max(worst, float(g.max()))
        if control:
            ctrl_worst = max(ctrl_worst, float(c.max()))
        n_tok += len(r.output)
    out = {"max_logit_gap": worst, "requests": len(reqs), "tokens": n_tok,
           "adopted": sum(1 for r in reqs if r.prefix_tokens > 0),
           "strict": sum(1 for r in reqs if r.mode.name == "STRICT")}
    if control:
        out["control_logit_gap"] = ctrl_worst
    return out


def replay(entries) -> Dict[int, Dict[int, int]]:
    """Each live sequence's committed extents {page index: physical page},
    rebuilt from the log alone: a page publish sets one extent, an unlink
    drops the sequence, a truncate keeps the pages below its offset."""
    from repro.core.oplog import OP_KV_COMMIT, OP_TRUNCATE, OP_UNLINK

    out: Dict[int, Dict[int, int]] = {}
    for e in entries:
        if e.op == OP_KV_COMMIT:
            out.setdefault(e.inode, {})[e.offset] = e.staging_addr
        elif e.op == OP_UNLINK:
            out.pop(e.inode, None)
        elif e.op == OP_TRUNCATE and e.inode in out:
            out[e.inode] = {i: p for i, p in out[e.inode].items()
                            if i < e.offset}
    return out


def publishes(entries) -> int:
    """Page publishes in the log."""
    from repro.core.oplog import OP_KV_COMMIT

    return sum(e.op == OP_KV_COMMIT for e in entries)


def strict_log_mismatch(entries, live: Dict[int, Dict[int, int]],
                        finished_pages: int, live_pages: int) -> Tuple[int, str]:
    """Count of disagreements between the operation log and the engine's
    metadata.  ``live`` maps each live STRICT sequence to its committed
    extents; ``finished_pages`` is the full pages of finished STRICT
    requests, ``live_pages`` the committed pages of live ones."""
    bad, why = 0, []
    got = replay(entries)
    for sid, ext in live.items():
        if got.get(sid, {}) != ext:
            bad += 1
            why.append(f"seq {sid} replays {len(got.get(sid, {}))} "
                       f"pages, holds {len(ext)}")
    for sid in set(got) - set(live):
        bad += 1
        why.append(f"finished seq {sid} still replays")
    commits = publishes(entries)
    if commits != finished_pages + live_pages:
        bad += 1
        why.append(f"{commits} publishes, {finished_pages} + {live_pages} "
                   f"full pages")
    return bad, "; ".join(why[:5])
