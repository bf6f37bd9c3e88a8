"""Speculative decoding: a draft model proposes, the target verifies.

The reference serves through vLLM, whose speculative mode is a headline
throughput feature; ours is rebuilt on the paged TPU engine and SERVED
through the scheduler's batch=1 fast path (``Scheduler(draft_engine=...)``,
``serve.py --draft-model``): speculation engages exactly when the chip is
latency-bound (one request in flight) and steps aside when lockstep
batching already fills the MXU.  Acceptance counters surface in
``/metrics`` (``istpu_spec_*``).  Per round:

1. the DRAFT engine scan-decodes ``k`` proposal tokens (cheap model, its own
   paged cache);
2. the TARGET engine scores ``[last_accepted_token, p_1..p_k]`` in ONE
   multi-token paged forward (``InferenceEngine.verify``) — one dispatch
   instead of ``k``;
3. proposals are accepted per the decision rule (below), then a token from
   the target's own distribution is appended, so every round emits between
   1 and k+1 tokens;
4. the draft is resynced by verifying the accepted tail against its own
   cache (rewrites of already-correct slots are harmless — position-masked
   attention and slot overwrite semantics, see ``verify``'s docstring).

SINGLE-SYNC STRUCTURE (round 11): on the fused path an entire decode
chunk costs ONE blocking host sync — the ``AdaptiveRController`` sizes
each dispatch's round count from a per-request acceptance EWMA
(``ISTPU_SPEC_ADAPTIVE`` / ``ISTPU_SPEC_R_BUCKETS``), the compiled
program clamps emission at the budget and returns bonus logits +
per-row counts itself (no host-side trim/reconcile dispatches), and
follow-up dispatches are enqueued from device-resident state before the
previous tokens land (``copy_to_host_async`` double-buffering).
tests/test_perf_smoke.py guards the 1-dispatch/1-sync structure.

Decision rules:

* ``sample="greedy"`` (default): accept while the proposal matches the
  target's argmax; output is EXACTLY the target's greedy decode —
  speculation changes the dispatch count, not the decision rule
  (property-tested in tests/test_speculative.py).  Exactness holds to the
  extent the verify forward's numerics match the scan decode's: in bf16 the
  batched einsum's reduction order can flip an argmax between near-tied
  logits, so low-precision serving should treat the guarantee as
  statistical rather than bitwise.
* ``sample="categorical"``: REJECTION SAMPLING (Leviathan et al. 2023 /
  the vLLM rule): draft token ``x_i ~ q_i`` is accepted with probability
  ``min(1, p_i(x_i) / q_i(x_i))``; on the first rejection a replacement is
  drawn from the residual ``norm(max(p_i - q_i, 0))`` and the round ends;
  if all ``k`` survive, a bonus token is drawn from ``p_{k+1}``.  This
  provably makes every emitted token an exact sample from the target's
  post-truncation distribution (temperature / top-k / top-p included —
  both p and q come from the same ``_truncate_logits`` math), regardless
  of draft quality.  Statistically verified in tests/test_speculative.py
  (chi-squared over the support).
"""

from __future__ import annotations

import math
import os
from collections import deque
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import stepprof as _stepprof
from .engine import (
    _ARGMAX_I32,
    _JIT_CACHE,
    _SPLIT2,
    _SPLIT3,
    _STACK_ROWS,
    _truncate_logits,
    _UNSTACK_ROWS,
    InferenceEngine,
    SequenceState,
)

_ROW_NEG1 = jax.jit(lambda l: l[-1])


def _parse_r_buckets(spec: Optional[str]) -> Tuple[int, ...]:
    """Parse ``ISTPU_SPEC_R_BUCKETS`` ("1,2,8") into a sorted, deduped,
    BOUNDED tuple.  Every bucket compiles a whole fused-rounds program
    (dozens of inlined forwards), so the set is clamped to at most 4
    values in [1, 32] — a bounded set is what keeps the steady-state
    retrace count at zero; garbage falls back to the default."""
    default = (1, 2, 8)
    if not spec:
        return default
    try:
        vals = sorted({int(x) for x in spec.split(",") if x.strip()})
    except ValueError:
        return default
    vals = [v for v in vals if 1 <= v <= 32]
    if not vals:
        return default
    return tuple(vals[:4])


class AdaptiveRController:
    """Acceptance-adaptive rounds-per-dispatch: an EWMA of tokens
    emitted per fused round sizes the next dispatch's round count R
    from a small FIXED bucket set.

    Why: a fused dispatch costs one host sync however many rounds it
    runs, so R should be just large enough that the dispatch's expected
    yield (``R * EWMA``) covers the chunk budget — a strong draft at
    ~full acceptance covers a 32-token chunk in one 8-round dispatch
    (one sync), while a weak draft walks the EWMA down and stops paying
    for rounds that mostly re-verify rejections.  The bucket set stays
    bounded (⇒ bounded compiled-program count ⇒ bounded retraces); the
    controller is carried PER REQUEST across scheduler steps
    (``SpeculativeDecoder._controller``), so acceptance learned on one
    chunk sizes the next.

    Hysteresis: stepping DOWN to a smaller bucket requires the smaller
    program's expected yield to beat the remaining budget by a margin
    (``hysteresis``); staying put and stepping up need none — an EWMA
    wobbling around a bucket boundary therefore settles instead of
    flapping between two compiled programs.

    Pure host math (no jax), unit-tested with injected acceptance
    sequences in tests/test_speculative.py."""

    def __init__(self, k: int, buckets: Sequence[int] = (1, 2, 8),
                 alpha: float = 0.4, hysteresis: float = 0.25):
        assert buckets and all(b >= 1 for b in buckets), buckets
        self.k = k
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        self.alpha = float(alpha)
        self.hysteresis = float(hysteresis)
        # optimistic start: a fresh request assumes full acceptance, so
        # its first dispatch is sized to cover the whole chunk (the
        # single-sync fast path); a weak draft walks the EWMA down
        self.rate = float(k + 1)
        self._bucket = self.buckets[-1]

    def update(self, tokens: int, rounds: int) -> None:
        """Fold one dispatch's observation: ``tokens`` emitted over
        ``rounds`` effective (unclamped) rounds."""
        if rounds <= 0:
            return
        self.rate += self.alpha * (tokens / rounds - self.rate)
        self.rate = min(max(self.rate, 1.0), float(self.k + 1))

    def suggest(self, remaining: int) -> int:
        """Bucket for the next dispatch given ``remaining`` tokens of
        budget: the smallest bucket whose expected yield covers it
        (with the down-switch margin), else the largest."""
        if remaining <= 0:
            return self.buckets[0]
        choice = self.buckets[-1]
        for b in self.buckets:
            margin = 1.0 + self.hysteresis if b < self._bucket else 1.0
            if b * self.rate >= remaining * margin:
                choice = b
                break
        self._bucket = choice
        return choice


def _build_fused_rounds(target: InferenceEngine, draft: InferenceEngine,
                        k: int, R: int, variant: str = "greedy"):
    """Compile ``R`` complete speculation rounds (draft k-token propose →
    target verify → accept/reject → draft resync) into ONE dispatch.

    The host speculation loop costs 2+ device syncs per round.  Fusing the
    whole round chain means one sync per R rounds — the same batching
    trick as the decode scan, applied to the propose/verify/resync
    pipeline.  Whether rounds-per-dispatch pays is not measured on a
    directly attached chip (ROADMAP A5).

    ``variant``: "greedy" (accept while the draft matches the target's
    argmax — output equals the target's greedy decode), or the stochastic
    rejection-sampling modes "plain" / "filter" (the module-docstring
    rule, with/without top-k/top-p truncation; identical math to
    ``_spec_decide``, run inline).  Stochastic draws derive from a base
    key folded with the token's ABSOLUTE position (draft samples) or the
    round's accepted length (accept/resample draws), so a fixed key
    reproduces its stream regardless of R bucketing or call boundaries.

    Device-side state per round: ``n`` (accepted length), a ``k+2``-token
    window of the newest accepted ids (enough to seed the next verify and
    the draft resync), the draft's running logits, and both paged caches.
    All shapes static: the draft resync always re-verifies a k+1 window
    (rewriting already-correct slots is harmless — position-masked
    attention, idempotent slot writes), so no per-width recompiles.

    DEVICE-RESIDENT RECONCILE: each row carries its budget ``n_max`` and
    every round's emission count is clamped to it ON DEVICE (``cnt =
    min(m+1, n_max - n)``), so a chunk never overshoots — the old
    host-side trim (one ``_resync_draft`` + one ``target.verify``
    tail-refresh, 2+ dispatches per fused call) is gone.  Rounds at the
    budget still execute (a scan has a fixed trip count) but emit
    nothing and leave the carried state untouched; the program's final
    width-1 verify rewrites the last ACCEPTED token's KV slot and
    returns the bonus-token logits, so both engines come back
    decode-ready at exactly the budget inside the same dispatch.  The
    per-round draft resync and the final refresh use the last-row-only
    verify binding (``_verify_last_jit``): only the next-token
    distribution is needed, so k wasted ``[dim, V]`` lm_head
    projections per round are skipped.

    Returns a jitted ``fn(t_params, d_params, t_cache, d_cache,
    t_table [B, W], d_table [B, W], n0 [B], n_max [B], win0 [B, k+2],
    d_logits0 [B, V], key, temp [B], tk [B], tp [B]) ->
    (outs [R, B, k+1], cnts [R, B], ms [R, B], n_final [B],
    win_final [B, k+2], t_logits [B, V], d_logits [B, V], t_cache,
    d_cache)`` with both caches donated (key/temp/tk/tp are ignored
    under "greedy").  ``cnts`` are budget-clamped emission counts (the
    tokens the host adopts); ``ms`` the RAW per-round accepted-proposal
    counts (acceptance accounting must see overshoot rounds too, or a
    clamped tail round would dilute a perfect draft's rate).
    ``n_final``/``win_final``/``d_logits`` feed the NEXT dispatch
    without any host round-trip — the async-readback pipeline enqueues
    dispatch N+1 from them before dispatch N's tokens land.  B is the
    lockstep speculation batch; the program re-specializes per (B,
    table width).
    """
    assert variant in ("greedy", "plain", "filter"), variant
    key = ("spec_fused", target._decode_raw, draft._decode_raw,
           target._verify_jit, draft._verify_last_jit,
           target._verify_last_jit,
           target.pc.block_tokens, k, R, variant)
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        return fn
    T = target.pc.block_tokens
    t_verify = target._verify_jit
    t_verify_last = target._verify_last_jit
    d_verify_last = draft._verify_last_jit
    d_decode = draft._decode_raw

    def rounds(t_params, d_params, t_cache, d_cache, t_table, d_table,
               n0, n_max, win0, d_logits0, base_key, temp, tk, tp):
        # Everything is BATCHED over B rows in lockstep: n/win/d_logits
        # carry a leading [B]; the draft/verify forwards are the engines'
        # ordinary batched steps; acceptance runs per row.  temp/tk/tp are
        # per-row [B] vectors (ignored under "greedy").
        B = win0.shape[0]
        if variant != "greedy":
            key_draft, key_acc = jax.random.split(base_key)
            row_keys_d = jax.random.split(key_draft, B)
            row_keys_a = jax.random.split(key_acc, B)

        def trunc(logits, temp_r, tk_r, tp_r):
            """Post-truncation logits rows [S, V] with per-row params —
            the same math as the decode scan's pick(), so p and q match
            what plain decode samples from."""
            l = logits.astype(jnp.float32) / jnp.maximum(temp_r, 1e-6)[:, None]
            if variant == "filter":
                l = _truncate_logits(l, tk_r, tp_r)
            return l

        def row_gather(table, idx):
            # table [B, W], idx [B, S] -> [B, S]
            return jnp.take_along_axis(table, idx, axis=1)

        def round_body(carry, _):
            t_cache, d_cache, n, win, d_logits = carry

            # 1. draft proposes k tokens per row (inline scan): argmax
            # under greedy, a categorical draw from its own post-
            # truncation distribution q_i otherwise (collected for the
            # accept test)
            def dstep(c, i):
                d_cache, logits = c  # logits [B, V]
                pos = n + i  # [B]
                if variant == "greedy":
                    tok = jnp.argmax(logits, -1).astype(jnp.int32)
                    q_i = jnp.zeros((B,), jnp.float32)  # placeholder
                else:
                    l = trunc(logits, temp, tk, tp)
                    subs = jax.vmap(jax.random.fold_in)(row_keys_d, pos)
                    tok = jax.vmap(jax.random.categorical)(subs, l).astype(
                        jnp.int32
                    )
                    q_i = jax.nn.softmax(l, axis=-1)  # [B, V]
                blk = row_gather(d_table, (pos // T)[:, None])[:, 0]
                lg2, d_cache = d_decode(
                    d_params, tokens=tok, positions=pos,
                    cache=d_cache, block_table=d_table,
                    seq_lens=pos + 1, slot_block_ids=blk,
                    slot_ids=pos % T,
                )
                return (d_cache, lg2), (tok, q_i)

            (d_cache, _), (props_kb, qs_kb) = jax.lax.scan(
                dstep, (d_cache, d_logits), jnp.arange(k)
            )
            props = jnp.transpose(props_kb)  # [B, k]

            # 2. target scores [prev, p_1..p_k] per row in one verify
            run = jnp.concatenate([win[:, -1:], props], axis=1)  # [B, k+1]
            poss = n[:, None] - 1 + jnp.arange(k + 1)[None]  # [B, k+1]
            blks = row_gather(t_table, poss // T)
            lgs, t_cache = t_verify(
                t_params, tokens=run, positions=poss,
                cache=t_cache, block_table=t_table,
                slot_block_ids=blks, slot_ids=poss % T,
            )  # lgs [B, k+1, V]

            # 3. acceptance, per row
            tail = jnp.concatenate([props, props[:, -1:]], axis=1)
            if variant == "greedy":
                choices = jnp.argmax(lgs, -1).astype(jnp.int32)  # [B, k+1]
                ok = props == choices[:, :k]
                m = jnp.where(
                    jnp.all(ok, axis=1), k, jnp.argmin(ok, axis=1)
                )  # [B]
                picked = jnp.take_along_axis(
                    choices, m[:, None], axis=1
                )[:, 0]
                e = jnp.where(
                    jnp.arange(k + 1)[None] == m[:, None],
                    picked[:, None], tail,
                )
            else:
                # rejection sampling (the _spec_decide math, per row):
                # accept x_i w.p. min(1, p_i(x_i)/q_i(x_i)); on the first
                # rejection draw from norm(max(p_m - q_m, 0)); all-k
                # accepted draws the bonus from p_{k+1} (q = 0 row)
                V = lgs.shape[-1]
                p = jax.nn.softmax(
                    trunc(
                        lgs.reshape(B * (k + 1), V),
                        jnp.repeat(temp, k + 1),
                        jnp.repeat(tk, k + 1),
                        jnp.repeat(tp, k + 1),
                    ),
                    axis=-1,
                ).reshape(B, k + 1, V)
                qs = jnp.transpose(qs_kb, (1, 0, 2))  # [B, k, V]
                us = jax.vmap(
                    lambda kb, nb: jax.random.uniform(
                        jax.random.fold_in(kb, nb), (k + 1,)
                    )
                )(row_keys_a, n)  # [B, k+1]
                px = jnp.take_along_axis(
                    p[:, :k], props[..., None], axis=2
                )[..., 0]  # [B, k]
                qx = jnp.take_along_axis(
                    qs, props[..., None], axis=2
                )[..., 0]
                acc = (qx > 0) & (us[:, :k] < jnp.minimum(1.0, px / qx))
                all_acc = jnp.all(acc, axis=1)  # [B]
                m = jnp.where(all_acc, k, jnp.argmin(acc, axis=1))
                pm = jnp.take_along_axis(
                    p, m[:, None, None], axis=1
                )[:, 0]  # [B, V]
                qm = jnp.where(
                    all_acc[:, None],
                    jnp.zeros_like(pm),
                    jnp.take_along_axis(
                        qs, jnp.minimum(m, k - 1)[:, None, None], axis=1
                    )[:, 0],
                )
                residual = jnp.maximum(pm - qm, 0.0)
                dist = jnp.where(
                    residual.sum(axis=1, keepdims=True) > 0, residual, pm
                )
                cdf = jnp.cumsum(dist, axis=1)
                r = us[:, k] * cdf[:, -1]
                repl = jnp.clip(
                    jnp.sum(cdf <= r[:, None], axis=1), 0, dist.shape[1] - 1
                ).astype(jnp.int32)
                e = jnp.where(
                    jnp.arange(k + 1)[None] == m[:, None],
                    repl[:, None], tail,
                )
            # device-resident reconcile: clamp emission at each row's
            # budget.  A row at n == n_max keeps executing (static trip
            # count) but emits 0 and carries its state unchanged — the
            # proposals it still writes land past the budget, in pages
            # the caller sized for exactly this overshoot (rem + k).
            cnt = jnp.minimum(m + 1, n_max - n)  # [B]
            n2 = n + cnt
            # newest k+2 accepted ids per row: win ++ e[:cnt], last k+2
            allw = jnp.concatenate([win, e], axis=1)  # [B, 2k+3]
            win2 = jnp.take_along_axis(
                allw, cnt[:, None] + jnp.arange(k + 2)[None], axis=1
            )

            # 4. draft resync: re-verify the last k+1 accepted tokens.
            # Fixed width on purpose — a lax.cond width-1 fast branch for
            # all-accepted rounds (the host loop's "clean" trick) was
            # MEASURED SLOWER here: branching on the carried paged cache
            # makes XLA materialize cache copies that dwarf the saved
            # forward.  Rewriting already-correct slots is harmless.
            # Last-row-only logits: the resync only needs the
            # next-token distribution to seed the next round's draft.
            poss_d = n2[:, None] - 1 - k + jnp.arange(k + 1)[None]
            blks_d = row_gather(d_table, poss_d // T)
            dlgs, d_cache = d_verify_last(
                d_params, tokens=win2[:, 1:], positions=poss_d,
                cache=d_cache, block_table=d_table,
                slot_block_ids=blks_d, slot_ids=poss_d % T,
            )
            return (t_cache, d_cache, n2, win2, dlgs[:, -1]), (e, cnt, m)

        carry0 = (t_cache, d_cache, n0, win0, d_logits0)
        (t_cache, d_cache, nF, winF, d_logitsF), (outs, cnts, ms) = \
            jax.lax.scan(round_body, carry0, None, length=R)
        # leave the target decode-ready: logits after each row's last
        # accepted token (its KV slot is rewritten in place — same
        # contract as the old host-side tail-refresh verify, but inside
        # the same dispatch)
        posF = nF[:, None] - 1  # [B, 1]
        lgT, t_cache = t_verify_last(
            t_params, tokens=winF[:, -1:], positions=posF,
            cache=t_cache, block_table=t_table,
            slot_block_ids=row_gather(t_table, posF // T),
            slot_ids=posF % T,
        )
        return (outs, cnts, ms, nF, winF, lgT[:, -1], d_logitsF,
                t_cache, d_cache)

    fn = jax.jit(rounds, donate_argnums=(2, 3))
    _JIT_CACHE[key] = fn
    return fn


@partial(jax.jit, static_argnums=(6,))
def _spec_decide(logits, q, toks, key, temperature, tk_tp, use_filter):
    """The rejection-sampling decision, entirely on device (one dispatch,
    two scalars downloaded).  Mirrors the module-docstring rule over the
    target's post-truncation p (same ``_truncate_logits`` math as the
    decode scan) against the draft's as-sampled q:

    accept x_i while ``u_i < min(1, p_i(x_i)/q_i(x_i))``; at the first
    rejection draw from ``norm(max(p_m - q_m, 0))`` (falling back to p_m
    when the residual vanishes); if all k survive, draw the bonus from
    ``p_{k+1}`` (encoded as the residual against q=0).  Returns (m, repl):
    the accepted-prefix length and the replacement/bonus token."""
    top_k_s, top_p_s = tk_tp
    k = toks.shape[0]
    rows = logits.shape[0]  # k + 1
    l = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    if use_filter:
        l = _truncate_logits(
            l,
            jnp.full((rows,), top_k_s, jnp.int32),
            jnp.full((rows,), top_p_s, jnp.float32),
        )
    p = jax.nn.softmax(l, axis=-1)  # [k+1, V]
    us = jax.random.uniform(key, (k + 1,))
    idx = jnp.arange(k)
    px = p[idx, toks]
    qx = q[idx, toks].astype(jnp.float32)
    acc = (qx > 0) & (us[:k] < jnp.minimum(1.0, px / qx))
    all_acc = jnp.all(acc)
    m = jnp.where(all_acc, k, jnp.argmin(acc))
    pm = p[m]
    qm = jnp.where(
        all_acc, jnp.zeros_like(pm), q[jnp.minimum(m, k - 1)].astype(jnp.float32)
    )
    residual = jnp.maximum(pm - qm, 0.0)
    # residual can vanish (p <= q on q's support): draw from p_m directly;
    # the bonus row rides the same branch (q = 0 -> residual = p_k)
    dist = jnp.where(residual.sum() > 0, residual, pm)
    cdf = jnp.cumsum(dist)
    repl = jnp.clip(
        jnp.searchsorted(cdf, us[k] * cdf[-1], side="right"),
        0, dist.shape[0] - 1,
    )
    return m, repl


class SpeculativeDecoder:
    def __init__(
        self,
        target: InferenceEngine,
        draft: InferenceEngine,
        k: int = 4,
    ):
        assert target.pc.block_tokens == draft.pc.block_tokens, (
            "target and draft must page with the same chunk size"
        )
        self.target = target
        self.draft = draft
        self.k = k
        # greedy rounds fuse into one dispatch per R rounds (see
        # _build_fused_rounds); turn off to force the host round loop
        self.fuse_rounds = True
        # acceptance-adaptive rounds-per-dispatch (AdaptiveRController):
        # ISTPU_SPEC_ADAPTIVE=0 pins the legacy static policy (largest
        # bucket until the tail, no pipelined readback); the bucket SET
        # comes from ISTPU_SPEC_R_BUCKETS either way, so the compiled-
        # program universe stays bounded and identical across modes
        self.adaptive = os.environ.get("ISTPU_SPEC_ADAPTIVE", "1") != "0"
        self.r_buckets = _parse_r_buckets(
            os.environ.get("ISTPU_SPEC_R_BUCKETS")
        )
        # per-request controllers keyed by TARGET seq id, carried across
        # scheduler steps (the scheduler forgets them at retirement);
        # bounded so a library caller who never retires can't grow it
        self._ctls: Dict[int, AdaptiveRController] = {}
        # round accounting for reporting acceptance rates
        self.rounds = 0
        self.accepted = 0
        self.proposed = 0
        self._rng = jax.random.PRNGKey(0)

    def _controller(self, st: SequenceState) -> AdaptiveRController:
        ctl = self._ctls.get(st.seq_id)
        if ctl is None:
            if len(self._ctls) >= 512:
                self._ctls.pop(next(iter(self._ctls)))
            ctl = self._ctls[st.seq_id] = AdaptiveRController(
                self.k, self.r_buckets
            )
        return ctl

    def forget(self, seq_id: int) -> None:
        """Drop the per-request adaptive-R state (called by the
        scheduler when the request retires)."""
        self._ctls.pop(seq_id, None)

    def prefill(self, tokens: Sequence[int]) -> Tuple[SequenceState, SequenceState]:
        return self.target.prefill(tokens), self.draft.prefill(tokens)

    def _resync_draft(self, st_d: SequenceState, accepted: List[int],
                      clean: bool = False) -> None:
        """Bring the draft's cache and logits in line with the accepted
        sequence.  The draft speculated past the rejection point, so its
        tokens are rewound and the accepted tail is re-verified; the
        window ending at the last accepted token takes one of exactly TWO
        widths (k+1, or 1 on clean rounds), bounding the compile count.

        ``clean=True`` (the all-accepted round): every draft-cache slot up
        to the bonus token already holds the RIGHT tokens' KV — the draft
        itself decoded them — so only the bonus token needs verifying, a
        width-1 dispatch instead of k+1 (the common case at high
        acceptance, where this saves most of the resync cost)."""
        st_d.tokens = list(accepted)
        w = 1 if clean else min(len(accepted), self.k + 1)
        run = accepted[-w:]
        logits = self.draft.verify(st_d, run, len(accepted) - w)
        st_d.last_logits = _ROW_NEG1(logits)

    def decode(
        self,
        st_t: SequenceState,
        st_d: SequenceState,
        n_steps: int,
        sample: str = "greedy",
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        rng: Optional[jax.Array] = None,
    ) -> List[int]:
        """Emit exactly ``n_steps`` tokens.  Greedy mode is equivalent to
        ``target.decode(st_t, n_steps)``; categorical mode draws every token
        from the target's post-truncation sampling distribution (rejection
        sampling — see module docstring)."""
        assert sample in ("greedy", "categorical"), sample
        if (
            self.fuse_rounds
            and self.target._has_verify
            and self.draft._has_verify
            and self.target.lora is None
            and self.draft.lora is None
            and len(st_t.tokens) >= self.k + 2
            and len(st_t.tokens) == len(st_d.tokens)
            and st_t.tokens[-(self.k + 2):] == st_d.tokens[-(self.k + 2):]
        ):
            if sample == "greedy":
                variant = "greedy"
            else:
                variant = "filter" if (top_k > 0 or top_p < 1.0) else "plain"
            if rng is None and sample == "categorical":
                self._rng, rng = _SPLIT2(self._rng)
            return self._decode_fused(
                st_t, st_d, n_steps, variant=variant,
                temperature=temperature, top_k=top_k, top_p=top_p, rng=rng,
            )
        if rng is None:
            self._rng, rng = _SPLIT2(self._rng)
        out: List[int] = []
        try:
            out = self._rounds(st_t, st_d, n_steps, sample, temperature,
                               top_k, top_p, rng)
        except MemoryError:
            # an allocator (draft or target) ran dry mid-round.  Mid-decode
            # the target state is NOT decode-ready — the round's final
            # emitted token's KV is only written by the NEXT round's verify
            # and ``last_logits`` is only refreshed at the successful end —
            # so a caller falling back to the plain decode path would
            # silently resample stale logits over an unwritten KV slot.
            # Re-verify the tail to restore decode-readiness, then
            # propagate (if the TARGET is the dry pool this raises again,
            # exactly like the plain batch=1 path would).
            st_t.last_logits = _ROW_NEG1(self.target.verify(
                st_t, [st_t.tokens[-1]], len(st_t.tokens) - 1
            ))
            raise
        excess = len(out) - n_steps
        if excess:
            del out[n_steps:]
            del st_t.tokens[-excess:]
            self._resync_draft(st_d, list(st_t.tokens))
        # verify rounds do not carry logits for the bonus token, so refresh
        # last_logits to leave the target state decode()-ready
        st_t.last_logits = _ROW_NEG1(self.target.verify(
            st_t, [st_t.tokens[-1]], len(st_t.tokens) - 1
        ))
        return out

    def _acquire_for(self, eng: InferenceEngine, st: SequenceState,
                     n_new: int, base_len: Optional[int] = None) -> None:
        """Grow ``st``'s page list to cover ``n_new`` more tokens (raises
        MemoryError with the state untouched — fused calls reconcile after
        every dispatch, so the state is always decode-ready here).
        ``base_len`` overrides ``len(st.tokens)`` as the starting length:
        the fused batch path sizes DRAFT pages from the TARGET length so a
        stale-shorter draft can never undersize its block table."""
        T = eng.pc.block_tokens
        need = -(-((base_len if base_len is not None
                    else len(st.tokens)) + n_new) // T)
        if need > len(st.block_ids):
            st.block_ids.extend(eng.pages.acquire(need - len(st.block_ids)))

    def _decode_fused(self, st_t: SequenceState, st_d: SequenceState,
                      n_steps: int, variant: str = "greedy",
                      temperature: float = 1.0, top_k: int = 0,
                      top_p: float = 1.0,
                      rng: Optional[jax.Array] = None) -> List[int]:
        return self._decode_fused_batch(
            [st_t], [st_d], n_steps, variant=variant,
            temperature=temperature, top_k=top_k, top_p=top_p, rng=rng,
        )[0]

    def decode_batch(
        self,
        st_ts: List[SequenceState],
        st_ds: List[SequenceState],
        n_steps: int,
        sample: str = "greedy",
        temperature=1.0,
        top_k=0,
        top_p=1.0,
        rng: Optional[jax.Array] = None,
    ) -> List[List[int]]:
        """Batched speculation: every row runs the fused propose/verify/
        accept/resync rounds in LOCKSTEP (one dispatch covers all rows'
        rounds), emitting exactly ``n_steps`` tokens per row.  Rows may
        have different lengths and (in categorical mode) different
        per-row temperature/top_k/top_p; ``sample`` is batch-wide.
        Requires fused eligibility for every row (verify-capable engines,
        no LoRA, len(tokens) >= k+2, draft in sync) — the host round loop
        has no batched form, so this raises otherwise."""
        assert sample in ("greedy", "categorical"), sample
        assert len(st_ts) == len(st_ds) and st_ts, (len(st_ts), len(st_ds))
        for st_t, st_d in zip(st_ts, st_ds):
            assert len(st_t.tokens) >= self.k + 2, (
                "batched speculation needs prompts of at least k+2 tokens"
            )
            # value equality alone is not enough: after a lockstep interlude
            # a sequence tail of >= k+2 repeated tokens would let a SHORTER
            # stale draft pass as synced, and draft page sizing below would
            # then run off the end of the draft block table
            assert (
                len(st_t.tokens) == len(st_d.tokens)
                and st_t.tokens[-(self.k + 2):] == st_d.tokens[-(self.k + 2):]
            ), "draft state out of sync with target"
        assert self.target._has_verify and self.draft._has_verify
        assert self.target.lora is None and self.draft.lora is None
        if sample == "greedy":
            variant = "greedy"
        else:
            tk_any = np.any(np.asarray(top_k) > 0)
            tp_any = np.any(np.asarray(top_p) < 1.0)
            variant = "filter" if (tk_any or tp_any) else "plain"
            if rng is None:
                self._rng, rng = _SPLIT2(self._rng)
        return self._decode_fused_batch(
            st_ts, st_ds, n_steps, variant=variant, temperature=temperature,
            top_k=top_k, top_p=top_p, rng=rng,
        )

    def _decode_fused_batch(
        self, st_ts: List[SequenceState], st_ds: List[SequenceState],
        n_steps: int, variant: str = "greedy", temperature=1.0,
        top_k=0, top_p=1.0, rng: Optional[jax.Array] = None,
    ) -> List[List[int]]:
        """Speculation with whole rounds compiled on device (greedy or
        stochastic — see _build_fused_rounds), batched over rows in
        lockstep.  One fused chunk costs ONE blocking host sync in the
        common case:

        * the per-request ``AdaptiveRController`` sizes R so the first
          dispatch's expected yield covers the whole budget;
        * the program clamps emission at each row's budget ON DEVICE
          (no overshoot, so the old 2-dispatch host trim is gone);
        * when acceptance disappoints and more dispatches are needed,
          the next one is enqueued from the PREVIOUS dispatch's
          device-resident outputs (n/window/draft-logits) BEFORE its
          tokens land on host, and every token download is kicked with
          ``copy_to_host_async`` at launch — the blocking ``np.asarray``
          mostly finds the bytes already waiting.

        Pages for the whole chunk (+k overshoot slack) are acquired up
        front when both pools can hold them; otherwise a degraded
        SERIAL mode sizes, acquires, and drains per dispatch, stepping R
        down through the bucket set under pressure (R = smallest bucket
        that still doesn't fit raises MemoryError out of the acquire —
        the host loop's "round can't fit" contract, with every
        completed dispatch's tokens already reconciled)."""
        k = self.k
        B = len(st_ts)
        T = self.target.pc.block_tokens
        outs_h: List[List[int]] = [[] for _ in range(B)]
        if n_steps <= 0:
            return outs_h
        if rng is None:
            rng = jax.random.PRNGKey(0)  # unused under "greedy"
        temp_d = jnp.asarray(
            InferenceEngine._per_row(temperature, B, np.float32))
        tk_d = jnp.asarray(InferenceEngine._per_row(top_k, B, np.int32))
        tp_d = jnp.asarray(InferenceEngine._per_row(top_p, B, np.float32))
        lens0 = [len(st.tokens) for st in st_ts]
        ctls = [self._controller(st) for st in st_ts]
        buckets = self.r_buckets

        def fits(grows: List[int]) -> bool:
            """Can both pools absorb per-row token growth ``grows``?
            Draft rows size from the TARGET length (stale-shorter
            drafts must never undersize their block tables)."""
            short_t = sum(
                max(0, -(-(len(st.tokens) + g) // T) - len(st.block_ids))
                for st, g in zip(st_ts, grows)
            )
            if short_t > self.target.free_pages:
                return False
            short_d = sum(
                max(0, -(-(len(t.tokens) + g) // T) - len(d.block_ids))
                for t, d, g in zip(st_ts, st_ds, grows)
            )
            return short_d <= self.draft.free_pages

        def acquire(grows: List[int]) -> None:
            for st, g in zip(st_ts, grows):
                self._acquire_for(self.target, st, g)
            for st_t, st, g in zip(st_ts, st_ds, grows):
                self._acquire_for(self.draft, st, g,
                                  base_len=len(st_t.tokens))

        # device-carried loop state: after the first dispatch these are
        # the previous program's outputs, so a follow-up dispatch needs
        # no host round-trip at all
        n_dev = jnp.asarray(lens0, jnp.int32)
        win_dev = jnp.asarray(
            [st.tokens[-(k + 2):] for st in st_ts], jnp.int32)
        dlog_dev = _STACK_ROWS(*[st.last_logits for st in st_ds])
        t_lg_dev = None
        t_table = d_table = n_max_d = None
        inflight: "deque" = deque()  # (outs, cnts, ms, R)
        # per-row progress bounds over confirmed + in-flight work:
        # floor assumes 1 token/round (every round emits >= 1 until the
        # budget clamp), exp uses the controller's EWMA
        floor_rows = [0] * B
        exp_rows = [0.0] * B

        def launch(R: int) -> None:
            nonlocal n_dev, win_dev, dlog_dev, t_lg_dev
            fn = _build_fused_rounds(
                self.target, self.draft, k, R, variant)
            # one compiled dispatch = R complete propose/verify/accept/
            # resync rounds for every row — the unit the step profiler's
            # accepted-per-dispatch attribution divides by
            _stepprof.note_dispatch("spec_round")
            (outs, cnts, ms, n_dev, win_dev, t_lg_dev, dlog_dev,
             t_cache, d_cache) = fn(
                self.target.params, self.draft.params,
                self.target.cache, self.draft.cache,
                t_table, d_table, n_dev, n_max_d, win_dev, dlog_dev,
                rng, temp_d, tk_d, tp_d,
            )
            self.target.cache = t_cache
            self.draft.cache = d_cache
            # async readback: kick the token D2H now, so the follow-up
            # dispatch (and the eventual blocking read) overlap it
            for arr in (outs, cnts, ms):
                try:
                    arr.copy_to_host_async()
                except AttributeError:  # non-array backends (tests)
                    pass
            inflight.append((outs, cnts, ms, R))
            for b in range(B):
                floor_rows[b] = min(n_steps, floor_rows[b] + R)
                exp_rows[b] = min(
                    float(n_steps), exp_rows[b] + R * ctls[b].rate)

        def drain() -> None:
            outs, cnts, ms, R = inflight.popleft()
            # the chunk's one BLOCKING host sync (the structural
            # single-sync guard in tests/test_perf_smoke.py counts it)
            _stepprof.note_sync("spec_tokens")
            h_outs = np.asarray(outs)   # [R, B, k+1]
            h_cnts = np.asarray(cnts)   # [R, B] budget-clamped
            h_ms = np.asarray(ms)       # [R, B] raw accepted proposals
            for b in range(B):
                new_toks: List[int] = []
                for r in range(R):
                    c = int(h_cnts[r, b])
                    if c:
                        new_toks.extend(
                            int(t) for t in h_outs[r, b, :c])
                outs_h[b].extend(new_toks)
                st_ts[b].tokens.extend(new_toks)
                st_ds[b].tokens = list(st_ts[b].tokens)
                eff = int((h_cnts[:, b] > 0).sum())
                if eff:
                    ctls[b].update(len(new_toks), eff)
            self.rounds += R * B
            self.proposed += R * B * k
            self.accepted += int(h_ms.sum())
            infl_R = sum(r for *_a, r in inflight)
            for b in range(B):
                conf = len(outs_h[b])
                floor_rows[b] = min(n_steps, conf + infl_R)
                exp_rows[b] = min(
                    float(n_steps), conf + infl_R * ctls[b].rate)

        def choose_R() -> int:
            if self.adaptive:
                return max(
                    ctls[b].suggest(
                        int(math.ceil(n_steps - exp_rows[b])))
                    for b in range(B)
                )
            # legacy static policy: largest bucket until the tail
            rem = n_steps - min(floor_rows)
            return (buckets[-1] if rem > 2 * (k + 1)
                    else buckets[min(1, len(buckets) - 1)])

        def settle_logits() -> None:
            # both engines decode-ready: the newest dispatch's final
            # in-program verify rewrote each row's last accepted token's
            # KV slot and produced the logits after it
            t_rows = _UNSTACK_ROWS(t_lg_dev)
            d_rows = _UNSTACK_ROWS(dlog_dev)
            for b in range(B):
                st_ts[b].last_logits = t_rows[b]
                st_ds[b].last_logits = d_rows[b]

        try:
            if fits([n_steps + k] * B):
                # fast path: the whole chunk's pages up front (budget +
                # k slack for the clamped rounds' past-budget writes),
                # one block table, one device-resident budget —
                # dispatches can pipeline freely
                acquire([n_steps + k] * B)
                t_table = self.target._block_table(st_ts)
                d_table = self.draft._block_table(st_ds)
                n_max_d = jnp.asarray(
                    [l + n_steps for l in lens0], jnp.int32)
                while True:
                    if (min(len(o) for o in outs_h) >= n_steps
                            and not inflight):
                        break
                    if not inflight:
                        launch(choose_R())
                    # double-buffer: when the in-flight work's EXPECTED
                    # yield still leaves budget, enqueue the next
                    # dispatch before this one's tokens land (adaptive
                    # mode only — the legacy policy keeps the old
                    # serial cadence)
                    if (self.adaptive and len(inflight) < 2
                            and min(exp_rows) < n_steps):
                        launch(choose_R())
                    drain()
            else:
                # degraded serial mode (memory pressure): size,
                # acquire, and drain per dispatch; R steps DOWN through
                # the bucket set until the growth fits, and the
                # smallest bucket that still doesn't fit raises out of
                # the acquire with every completed dispatch already
                # reconciled
                while min(len(o) for o in outs_h) < n_steps:
                    rems = [n_steps - len(o) for o in outs_h]
                    R = choose_R()
                    while True:
                        grows = [min(R * (k + 1), r + k) for r in rems]
                        if R == buckets[0] or fits(grows):
                            break
                        R = max(b for b in buckets if b < R)
                    acquire(grows)
                    t_table = self.target._block_table(st_ts)
                    d_table = self.draft._block_table(st_ds)
                    n_dev = jnp.asarray(
                        [len(st.tokens) for st in st_ts], jnp.int32)
                    n_max_d = jnp.asarray(
                        [len(st.tokens) + min(r, R * (k + 1))
                         for st, r in zip(st_ts, rems)], jnp.int32)
                    launch(R)
                    drain()
        except MemoryError:
            # a pool ran dry mid-chunk (degraded mode raises from the
            # acquire BEFORE a dispatch): every completed dispatch's
            # tokens are already on st.tokens, so restoring
            # decode-readiness is all that's left before the caller's
            # fallback takes over
            while inflight:
                drain()
            if t_lg_dev is not None:
                settle_logits()
            raise

        settle_logits()
        return outs_h

    def _rounds(self, st_t, st_d, n_steps, sample, temperature, top_k,
                top_p, rng) -> List[int]:
        out: List[int] = []
        while len(out) < n_steps:
            k = self.k
            if sample == "greedy":
                # 1. draft proposes k tokens (advances st_d by k)
                proposals = self.draft.decode(st_d, k)

                # 2. target scores [prev_token, p_1..p_k] in one dispatch;
                #    row j gives the target's choice AFTER consuming that
                #    row's token
                prev = st_t.tokens[-1]
                run = [prev] + proposals
                logits = self.target.verify(st_t, run, len(st_t.tokens) - 1)
                choices = np.asarray(_ARGMAX_I32(logits))  # [k+1]

                # 3. accept while the draft agreed, then take the target's
                #    token
                m = 0
                while m < k and proposals[m] == int(choices[m]):
                    m += 1
                emitted = proposals[:m] + [int(choices[m])]
            else:
                rng, r_draft, r_accept = _SPLIT3(rng)
                # 1. draft samples k tokens AND the exact distributions they
                #    came from (q_i after temperature/top-k/top-p) — q stays
                #    on device for the compiled decision step
                proposals, q = self.draft.propose(
                    st_d, k, temperature=temperature, top_k=top_k,
                    top_p=top_p, rng=r_draft,
                )

                # 2. target logits p_1..p_{k+1} from one verify, then the
                #    whole accept/reject/residual/bonus decision in ONE
                #    compiled dispatch; only (m, replacement) come to host
                prev = st_t.tokens[-1]
                run = [prev] + proposals
                logits = self.target.verify(st_t, run, len(st_t.tokens) - 1)
                use_filter = top_k > 0 or top_p < 1.0
                m_d, repl_d = _spec_decide(
                    logits, q, jnp.asarray(proposals, jnp.int32), r_accept,
                    jnp.float32(temperature), (top_k, float(top_p)),
                    use_filter,
                )
                m = int(m_d)
                emitted = proposals[:m] + [int(repl_d)]

            self.rounds += 1
            self.proposed += k
            self.accepted += m
            st_t.tokens.extend(emitted)
            out.extend(emitted)

            # 4. resync the draft onto the accepted sequence (width-1 when
            # every proposal survived: the draft cache is already right)
            self._resync_draft(st_d, list(st_t.tokens), clean=(m == k))
        return out

    def generate(self, tokens: Sequence[int], n_steps: int, **kw) -> List[int]:
        st_t, st_d = self.prefill(tokens)
        return self.decode(st_t, st_d, n_steps, **kw)

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.proposed if self.proposed else 0.0
