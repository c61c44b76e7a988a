"""The comparison that decides ``correct``.

Every number compared comes from what the timed window produced, at the
timed sizes, against a plain reference that imports nothing of the
program and takes no weight or table it made (the weights are the
benchmark's own; the IVF centroids are index state, read like the rows):

  stage1_rows   stage-1 results (ids and order) of every window pass that
                differ from the reference scan of the same index state,
                beyond near-ties (exact: limit 0)
  stage1_dsim   widest |similarity - reference| over those results
  embed_gap     widest 1 - cos(program embedding, reference), sampled
  judge_gap     widest |judge logit - reference logit|, sampled pairs
  decisions     lookups whose hit, served key or served value differ
                from the oracle's verdicts replayed on the same
                candidates, plus oracle scores that differ (exact: 0)
  inserts_lost  admitted entries missing from the index although never
                evicted (exact: 0)
  compiles      compilations inside the window (exact: 0)

The reference scan runs on the device at HIGHEST precision over a copy
of the index taken when the window opened, replaying the index's own
mutation log up to each pass. With ``control`` the reference is put in
the program's place one precision step down: stage 1 at HIGH (three
bf16 passes) for the fp32-at-HIGHEST scan, float8 for the bfloat16
models. Stage 1 is also read at DEFAULT (one bf16 pass), the step below
HIGH, and reported beside it (``stage1_default``). Limits are per
configuration, in its file (``limits``), set between the program's
readings and the control's (PERF.md).
"""
from __future__ import annotations

import zlib

import numpy as np

import model_ref

K_REF = 32          # reference candidates per query before the IVF filter
TIE_TOL = 1e-5      # scores closer than this are ties for row order
SAMPLE = 64         # embeddings and judge pairs compared per run


class ReferenceOracle:
    """Frozen copy of ``OracleJudge``'s scoring (src/repro/core/judge.py,
    commit 5e6ac64): seeded per (pair, nth scoring of the pair)."""

    def __init__(self, world, accuracy: float, seed: int,
                 max_pairs: int = 65536):
        self.world, self.acc, self.seed = world, accuracy, seed
        self.max_pairs = max_pairs
        self._pair_counts: dict = {}

    @staticmethod
    def _u01(x: int, salt: int) -> float:
        m = (1 << 64) - 1
        x = (x + salt * 0x9E3779B97F4A7C15) & m
        x ^= x >> 30
        x = (x * 0xBF58476D1CE4E5B9) & m
        x ^= x >> 27
        x = (x * 0x94D049BB133111EB) & m
        x ^= x >> 31
        return x / 2.0**64

    def score(self, q: str, c: str) -> float:
        n = self._pair_counts.pop((q, c), 0)
        self._pair_counts[(q, c)] = n + 1
        if len(self._pair_counts) > self.max_pairs:
            self._pair_counts.pop(next(iter(self._pair_counts)))
        ent = zlib.crc32(f"{q}\x00{c}".encode())
        base = (ent << 32) ^ (n << 8) ^ (self.seed & 0xFF)
        same = self.world.same_intent(q, c)
        correct = self._u01(base, 1) < self.acc
        positive = same if correct else not same
        u = self._u01(base, 2)
        if positive:
            return float(np.float32((1.0 - u) ** (1.0 / 30.0)))
        return float(np.float32(1.0 - (1.0 - u) ** (1.0 / 19.0)))


# ------------------------------------------------------------- stage 1

class _RefIndex:
    """The index as the window saw it: the copy taken at the open plus
    the mutation log, on the device."""

    def __init__(self, snap: dict):
        import jax
        import jax.numpy as jnp

        self.emb = jax.device_put(snap["emb"])
        self.active = snap["active"].copy()
        self.row_se = snap["row_se"].copy()
        self.done = 0

        @jax.jit
        def put(emb, rows, vals):
            return emb.at[rows].set(vals)

        self._put = put
        self._jnp = jnp

    def advance(self, muts, upto: int) -> None:
        rows, vals = [], []
        for m in muts[self.done:upto]:
            if m[0] == "add":
                _, row, se_id, emb = m
                self.active[row], self.row_se[row] = True, se_id
                rows.append(row)
                vals.append(emb)
            else:
                self.active[m[1]] = False
                self.row_se[m[1]] = -1
        self.done = upto
        for off in range(0, len(rows), 64):
            r = np.asarray(rows[off:off + 64], np.int32)
            v = np.stack(vals[off:off + 64])
            pad = 64 - len(r)
            if pad:   # a fixed shape: repeat the first write
                r = np.concatenate([r, np.repeat(r[:1], pad)])
                v = np.concatenate([v, np.repeat(v[:1], pad, 0)])
            self.emb = self._put(self.emb, r, v)

    def top(self, q: np.ndarray, precision):
        """(rows, scores) of the K_REF best active rows per query."""
        import jax

        jnp = self._jnp
        s = jnp.matmul(jnp.asarray(q), self.emb.T, precision=precision)
        s = jnp.where(jnp.asarray(self.active)[None, :], s, -jnp.inf)
        v, r = jax.lax.top_k(s, min(K_REF, s.shape[1]))
        return np.asarray(r), np.asarray(v, np.float64)


def _ivf_keep(q, rows, ref, router) -> tuple:
    """Which reference rows the routed scan could see: a row's cluster is
    its best centroid, a query probes its nprobe best live centroids.
    Also returns whether either choice was a near-tie."""
    cen = router["centroids"].astype(np.float64)
    live = router["live"]
    cs = np.where(live, cen @ q.astype(np.float64), -np.inf)
    order = np.argsort(-cs, kind="stable")
    nprobe = router["nprobe"]
    probed = set(order[:nprobe].tolist())
    near = nprobe < int(live.sum()) and \
        cs[order[nprobe - 1]] - cs[order[nprobe]] <= TIE_TOL
    e = np.asarray(ref.emb[ref._jnp.asarray(rows)], np.float64)
    rc = e @ cen.T
    own = np.argmax(rc, axis=1)
    srt = np.sort(rc, axis=1)
    near |= bool(np.any(srt[:, -1] - srt[:, -2] <= TIE_TOL))
    return np.array([o in probed for o in own]), near


def stage1(snap, log, router, control: bool):
    """Compare every window pass. Returns ([rows differing, widest dsim]
    of the program, then, with ``control``, of the reference at HIGH and
    at DEFAULT precision put in its place; queries compared)."""
    import jax

    ref = _RefIndex(snap)
    steps = (jax.lax.Precision.HIGH, jax.lax.Precision.DEFAULT) \
        if control else ()
    got = [[0, 0.0] for _ in range(1 + len(steps))]
    count = 0
    for p in log.passes:
        ref.advance(log.muts, p["at"])
        q = p["q"]
        rows, vals = ref.top(q, jax.lax.Precision.HIGHEST)
        lower = [ref.top(q, s) for s in steps]
        for i in range(len(q)):
            r, v = rows[i], vals[i]
            near = False
            if router is not None:
                keep, near = _ivf_keep(q[i], r, ref, router)
                r, v = r[keep], v[keep]
            want = (v >= p["gate"])
            want_rows, want_v = r[want][:p["k"]], v[want][:p["k"]]
            want_ids = ref.row_se[want_rows]
            ids, sims = p["out"][i]
            sides = [(np.asarray(ids, np.int64), np.asarray(sims))]
            for c_rows, c_vals in lower:
                cr, cv = c_rows[i], c_vals[i]
                if router is not None:
                    ck, _ = _ivf_keep(q[i], cr, ref, router)
                    cr, cv = cr[ck], cv[ck]
                g = cv >= p["gate"]
                sides.append((ref.row_se[cr[g][:p["k"]]], cv[g][:p["k"]]))
            count += 1
            for side, (ids, sims) in zip(got, sides):
                if len(ids) == len(want_ids) and \
                        np.array_equal(ids, want_ids):
                    if len(ids):
                        side[1] = max(side[1], float(
                            np.max(np.abs(sims - want_v))))
                elif not (near or _tie(ids, sims, want_v, p["gate"])):
                    side[0] += 1
    return got, count


def _tie(ids, sims, want_v, gate) -> bool:
    """Lists that differ only across ties: the same scores in order,
    and any extra entry on the gate's edge."""
    m = min(len(sims), len(want_v))
    if not np.allclose(np.sort(sims)[::-1][:m], want_v[:m], rtol=0,
                       atol=TIE_TOL):
        return False
    extra = np.concatenate([np.asarray(sims)[m:], want_v[m:]])
    return bool(np.all(np.abs(extra - gate) <= TIE_TOL))


# -------------------------------------------------------------- models

def _sample(n: int, rng) -> np.ndarray:
    return np.sort(rng.choice(n, min(n, SAMPLE), replace=False))


def embed_gap(model, max_len: int, weights, embeds, rng,
              control: bool) -> list:
    """Widest 1 - cos between the window's embeddings and the reference
    forward of the same texts by the embedder's ``model``
    (``model_ref.Model``): [program, control or None]."""
    texts, got = [], []
    for t, e in embeds:
        texts.extend(t)
        got.append(np.asarray(e, np.float64))
    if not texts:
        return [0.0, 0.0 if control else None]
    got = np.concatenate(got)
    pick = _sample(len(texts), rng)
    toks = model_ref.byte_tokens([texts[i] for i in pick], max_len,
                                 int(model.hf["vocab_size"]))
    ref = model_ref.embed_ref(model, weights, toks).astype(np.float64)

    def gap(mine):
        cos = np.sum(mine * ref, 1) / (np.linalg.norm(mine, axis=1)
                                       * np.linalg.norm(ref, axis=1))
        return float(np.max(1.0 - cos))

    return [gap(got[pick]), gap(model_ref.embed_ref(
        model, weights, toks, control=True)) if control else None]


def judge_gap(model, max_len: int, weights, calls, rng,
              control: bool) -> list:
    """Widest |logit - reference logit| over sampled judged pairs, by the
    judge's ``model`` (``model_ref.Model``): [program, control or None]."""
    pairs, scores = [], []
    for q, c, s in calls:
        pairs.extend(zip(q, c))
        scores.extend(np.asarray(s, np.float64).tolist())
    if not pairs:
        return [0.0, 0.0 if control else None]
    pick = _sample(len(pairs), rng)
    toks = model_ref.byte_tokens([f"{pairs[i][0]} [SEP] {pairs[i][1]}"
                                  for i in pick], max_len,
                                 int(model.hf["vocab_size"]))
    ref = model_ref.judge_logit_ref(model, weights, toks)
    s = np.clip(np.asarray(scores)[pick], 1e-12, 1 - 1e-12)
    out = [float(np.max(np.abs(np.log(s) - np.log1p(-s) - ref)))]
    if control:
        out.append(float(np.max(np.abs(model_ref.judge_logit_ref(
            model, weights, toks, control=True) - ref))))
    else:
        out.append(None)
    return out


# ----------------------------------------------------------- decisions

def decisions(world, dep, recorded_calls, win) -> int:
    """Replay the oracle from its first score; every window lookup's
    decision (``served.Answer``, as it was served) must be the
    reference's on the same candidates."""
    oracle = ReferenceOracle(world, dep["judge_accuracy"],
                             dep["oracle_seed"])
    tau = dep["cache"]["tau_lsm"]
    bad = 0
    by_batch = {}
    for batch, qs, cs, got in recorded_calls:
        ref = np.array([oracle.score(q, c) for q, c in zip(qs, cs)])
        bad += int(np.sum(ref != np.asarray(got, np.float64)))
        if batch >= 0:
            by_batch[batch] = (cs, ref)
    groups: dict = {}
    for i, b in enumerate(win["batch_of"]):
        if b >= 0:
            groups.setdefault(int(b), []).append(i)
    for b, idx in groups.items():
        cs, ref = by_batch.get(b, ([], np.zeros(0)))
        off = 0
        for i in idx:
            r = win["results"][i]
            m = r.judge_calls
            keys, sc = cs[off:off + m], ref[off:off + m]
            off += m
            if m == 0:
                bad += int(r.hit or r.n_candidates != 0)
                continue
            j = int(np.argmax(sc))
            if sc[j] >= tau:
                ok = r.hit and r.key == keys[j] and \
                    r.value == world.answer(keys[j])
            else:
                ok = not r.hit
            bad += int(not ok)
        bad += int(off != len(cs))
    return bad


def inserts_lost(index, rows_of, inserted, muts) -> int:
    """Entries admitted in the window that the index does not hold
    unchanged, and that no logged removal took out."""
    gone = set()
    for m in muts:
        if m[0] == "remove":
            gone.update(int(x) for x in m[2] if x >= 0)
    lost = 0
    for se_id, _, emb in inserted:
        if se_id in gone:
            continue
        row = rows_of.get(se_id)
        ok = row is not None and index.active[row] and \
            index.row_se[row] == se_id and \
            np.array_equal(index.emb[row], emb)
        lost += int(not ok)
    return lost


def verdict(values: dict, limits: dict) -> tuple:
    """(correct, compared): each number beside its limit."""
    compared = {k: {"value": values[k], "limit": limits[k]}
                for k in values}
    ok = all(values[k] <= limits[k] for k in values)
    return ok, compared
