"""Cross-replica divergence auditor — dp replicas must agree.

After a grad sync every dp replica holds (nominally) the same reduced
gradient.  On the native arms that agreement is BITWISE — XLA's ring
allreduce is deterministic for a fixed topology, so any bit that
differs across replicas is silent data corruption (a flipped DRAM bit,
a bad ICI lane, a miscompiled kernel), invisible to every
metadata-level sentry because the op/dtype/count/seq all still match.
On the quant / hier+quant arms the replicas see the same wire payload
but may accumulate in different orders, so the compare is
TOLERANCE-BOUNDED on the summary stats instead of bitwise.

The exchange rides the control plane (``ctx.bootstrap`` — the desync
sentinel's transport), NOT the possibly-corrupt data plane: each rank
publishes per-bucket blake2s digests + (l2, absmax) stats, reads every
peer's blob, and majority-votes.  The verdict names the first
divergent (step, bucket, rank): with >= 3 replicas the rank whose
digest disagrees with the majority IS the corrupted one; with 2 the
verdict reports the pair (attribution needs a quorum).
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence

from . import probes

KEY_PREFIX = "numerics:grads:"
PEER_TIMEOUT = 5.0            # per-peer blob fetch bound
_REL_TOL = 1e-4               # stat tolerance on the quant arms


def bucket_summary(x, arm: str = "native") -> Dict[str, Any]:
    """One bucket's compare record: blake2s digest of the raw bytes
    plus l2/absmax stats.  The digest drives the bitwise compare on
    native arms; the stats drive the tolerance compare on quant arms
    (and double as human-readable context either way)."""
    fp = probes.fingerprint(x)
    return {"digest": probes.payload_digest(x), "arm": arm,
            "l2": round(sum(fp["l2"]), 6),
            "absmax": round(max(fp["absmax"] or [0.0]), 6),
            "nonfinite": fp["total_nonfinite"]}


def publish(ctx, step: int, buckets: Sequence[Dict[str, Any]]) -> None:
    """Publish this rank's per-bucket records for ``step`` out-of-band.
    A dead control plane must not take down the training step."""
    blob = json.dumps({"step": int(step), "buckets": list(buckets)},
                      sort_keys=True)
    try:
        ctx.bootstrap.put(KEY_PREFIX + str(int(step)), blob)
    except Exception:
        pass


def _mismatch(mine: Dict[str, Any], theirs: Dict[str, Any]) -> bool:
    if mine.get("arm", "native") in ("native", "") \
            and theirs.get("arm", "native") in ("native", ""):
        return mine["digest"] != theirs["digest"]
    # quant / hier+quant: same wire payload, order-sensitive f32
    # accumulation — bound the stats instead of demanding bit equality
    for k in ("l2", "absmax"):
        a, b = float(mine.get(k, 0.0)), float(theirs.get(k, 0.0))
        if abs(a - b) > _REL_TOL * max(abs(a), abs(b), 1.0):
            return True
    return False


def audit(ctx, step: int, buckets: Sequence[Dict[str, Any]],
          peers: Optional[Sequence[int]] = None) -> Dict[str, Any]:
    """Publish this rank's records, gather every peer's, majority-vote.

    Returns ``{step, rank, compared, divergent: [...], missing,
    first}`` where each divergent row is ``{step, bucket, rank,
    digest, majority_digest}`` and ``first`` is the first divergent
    (step, bucket, rank) triple — the attribution the doctor arm
    asserts on.  ``divergent`` is ordered by bucket, so
    ``first`` names the earliest corrupted bucket."""
    publish(ctx, step, buckets)
    peers = list(peers if peers is not None else range(ctx.size))
    blobs: Dict[int, List[Dict[str, Any]]] = {ctx.rank: list(buckets)}
    missing: List[int] = []
    for peer in peers:
        if peer == ctx.rank:
            continue
        try:
            doc = json.loads(ctx.bootstrap.get(
                peer, KEY_PREFIX + str(int(step)), timeout=PEER_TIMEOUT))
            blobs[peer] = list(doc.get("buckets") or [])
        except Exception:
            missing.append(peer)
    out: Dict[str, Any] = {"step": int(step), "rank": int(ctx.rank),
                           "compared": sorted(blobs), "missing": missing,
                           "divergent": [], "first": None}
    n_buckets = min((len(b) for b in blobs.values()), default=0)
    for bi in range(n_buckets):
        recs = {r: blobs[r][bi] for r in sorted(blobs)}
        # majority digest over the native-compare view; quant arms vote
        # on the rounded stat tuple instead
        def _key(rec):
            if rec.get("arm", "native") in ("native", ""):
                return rec["digest"]
            return (rec.get("l2"), rec.get("absmax"))
        votes: Dict[Any, int] = {}
        for rec in recs.values():
            votes[_key(rec)] = votes.get(_key(rec), 0) + 1
        majority = max(votes, key=lambda k: votes[k])
        if len(votes) == 1:
            continue
        if len(recs) == 2:
            a, b = sorted(recs)
            out["divergent"].append({
                "step": int(step), "bucket": bi, "rank": -1,
                "pair": [a, b], "digest": recs[a].get("digest"),
                "majority_digest": recs[b].get("digest")})
            continue
        for r, rec in recs.items():
            if _key(rec) != majority \
                    and votes[_key(rec)] < votes[majority]:
                out["divergent"].append({
                    "step": int(step), "bucket": bi, "rank": r,
                    "digest": rec.get("digest"),
                    "majority_digest": (majority if isinstance(
                        majority, str) else None)})
    if out["divergent"]:
        first = out["divergent"][0]
        out["first"] = {"step": first["step"], "bucket": first["bucket"],
                        "rank": first["rank"]}
    return out


def format_verdict(v: Dict[str, Any]) -> str:
    """One-paragraph human rendering of an audit dict."""
    lines = [f"divergence auditor (rank {v['rank']}, step {v['step']}, "
             f"{len(v.get('compared', []))} replica(s) compared):"]
    for row in v.get("divergent", ()):
        if row.get("rank", -1) >= 0:
            lines.append(
                f"  DIVERGED: rank {row['rank']} bucket {row['bucket']} "
                f"digest {row['digest']} != majority "
                f"{row['majority_digest']} — silent data corruption on "
                "that replica")
        else:
            lines.append(
                f"  DIVERGED: bucket {row['bucket']} differs between "
                f"ranks {row.get('pair')} (2 replicas: no quorum to "
                "name the corrupt one)")
    if v.get("missing"):
        lines.append(f"  no records published by rank(s) {v['missing']}")
    if len(lines) == 1:
        lines.append("  every replica agrees — no divergence")
    return "\n".join(lines)
