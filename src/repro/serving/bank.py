"""Persistent sharded DeltaBank ring-buffer (the serving-side bank).

PR 2's :class:`repro.fl.engine.DeltaBank` dies with its simulator window:
banks are produced per inter-apply window and garbage-collected once every
row is applied.  A serving deployment has no "end of run" — personalization
traffic arrives forever and the global model advances in aggregation
*windows* — so :class:`DeltaRing` makes the bank persistent:

  * the banks AND the params snapshot of the last ``windows`` aggregation
    windows stay alive on device, keyed by window id and by user (a user's
    latest delta row is addressable until its window retires);
  * a row admitted with staleness τ > 0 — a straggler whose request was
    stamped in an earlier window — is folded into the *current* window's
    ``apply_rows`` weight vector via :func:`repro.core.admission_weights`
    (β/M with FedAsync damping ``(1+τ)^{-a}``) instead of being dropped:
    the bounded-staleness admission rule mirroring the paper's τ ≤ τ_max
    assumption.  Rows staler than ``tau_max`` ARE dropped (and counted);
  * the window apply routes through the non-donating
    :func:`repro.core.apply_admitted_rows`, because retained snapshots must
    outlive the apply (stragglers are computed against them).

Persistence scope: the ring persists *across windows* (device residency, no
host round-trip) AND — via :meth:`load` +
``PersonalizationServer.save/restore`` through ``repro.checkpoint.store`` —
its params snapshots, window counter and cumulative admission stats survive
process restarts (see :meth:`DeltaRing.load` for exactly which counters
persist and which are process-local).  What a restart still loses:
in-flight straggler delta rows (their banks are device-only); affected
users simply re-personalize against the restored snapshots.

Fairness: ``user_cap`` bounds the delta rows one user may have admitted
into a single window's apply (the ring is the admission authority; the
micro-batcher's matching cap refuses over-cap requests pre-cohort).

Partial-model personalization (``subset=``): with a ``personal_subset``
declared, every banked row and every retained snapshot holds only the
personal leaves (the pruned structure of ``repro.core.subset``) — the
shared backbone is stored ONCE (``_base``) and recombined on demand, so
per-user ring residency shrinks from full-model to subset bytes
(``row_nbytes``; the ``ring_bytes_per_user`` stat and bench gate).  This
is exact, not approximate: subset applies never touch backbone leaves, so
one backbone serves every retained window bit-for-bit.

Quantized delta banking (``delta_dtype="int8"``): the orthogonal residency
axis.  Retained banks arrive as :class:`repro.core.quant.QuantizedBank`
handles — int8 rows + per-row-per-leaf f32 scales (symmetric absmax,
chosen at admission by the server's flush, with per-user error feedback) —
so a banked row costs ~N bytes instead of 4N, and the window apply
dispatches through the fused dequant×weight×accumulate kernel
(``apply_rows_q`` via ``apply_admitted_rows``) without ever materializing
an fp32 row.  The ring additionally demotes the *personal leaves of
retired windows' snapshots* to int8 (:class:`repro.core.quant.QuantTree`,
per-leaf scale): the current window's snapshot stays exact fp32 — fresh
heads are never quantization-noisy — while straggler recomputes against
older windows transparently dequantize through :meth:`snapshot` /
:meth:`subset_snapshot`.  ``row_nbytes`` counts the quantized bytes and
``row_nbytes_fp32`` the fp32 baseline, which is what the ``quant`` bench's
≥ 3.5x ``ring_bytes_per_user`` gate measures.  The ring itself is
codec-agnostic at admission: it pins whatever bank handle the flush
retained and groups admitted rows per bank — fp32 and int8 windows can
even coexist during a migration.
"""
from __future__ import annotations

import warnings
from typing import Dict, List, Optional, Tuple

from repro.core import (admission_weights, apply_admitted_rows,
                        mask_rows, robust_flush_weights)
from repro.core.quant import (QuantStack, QuantTree, dequantize_tree,
                              fp32_row_nbytes, quantize_tree)
from repro.core.subset import SubsetSpec, merge_subset
from repro.core.subset import row_nbytes as _row_nbytes
from repro.core.types import ServerState
from repro.fl.engine import DeltaBank
from repro.spans import span


class DeltaRing:
    """Ring of the last ``windows`` aggregation windows of stacked deltas.

    ``retain`` is shaped as a :meth:`CohortEngine.add_bank_hook` callback —
    attaching the ring to an engine keeps every bank the engine produces
    alive for ``windows`` windows.  ``admit`` marks a specific (bank, row)
    as contributing to the next server apply; :meth:`advance` closes the
    window with one fused ``apply_rows`` pass per contributing bank.
    """

    def __init__(self, params0, *, windows: int = 4,
                 tau_max: Optional[int] = None,
                 user_cap: Optional[int] = None, subset=None,
                 delta_dtype: str = "fp32",
                 robust: Optional[str] = None,
                 clip_norm: Optional[float] = None,
                 trim_frac: float = 0.1):
        if windows < 1:
            raise ValueError("need at least one retained window")
        if delta_dtype not in ("fp32", "int8"):
            raise ValueError(f"delta_dtype must be 'fp32' or 'int8', "
                             f"got {delta_dtype!r}")
        if robust not in (None, "clip", "trim"):
            raise ValueError(f"robust must be None, 'clip' or 'trim', "
                             f"got {robust!r}")
        self.delta_dtype = delta_dtype
        # Byzantine-robust window apply: "clip" bounds each admitted row's
        # L2 norm, "trim" drops the norm tails of the window, both
        # calibrated over the whole window's admissions (see
        # repro.core.robust_flush_weights); either one also drops
        # non-finite rows, so a NaN-bombing user cannot poison the window
        self.robust = robust
        self.clip_norm = clip_norm
        self.trim_frac = trim_frac
        self.windows = windows
        # a straggler can only be recomputed against a retained snapshot,
        # so the EFFECTIVE staleness bound never exceeds the ring depth —
        # but the REQUESTED value is kept (and checkpointed): restoring
        # this ring into a deeper one must widen back to the request, not
        # keep the accidentally-tightened clamp.
        self.tau_max_requested = int(tau_max) if tau_max is not None \
            else windows - 1
        if tau_max is not None and tau_max > windows - 1:
            warnings.warn(
                f"tau_max={tau_max} exceeds the ring depth; clamped to "
                f"{windows - 1} (a straggler can only be recomputed against "
                f"a retained snapshot).  The requested value is preserved "
                f"for checkpoint round-trips.", stacklevel=2)
        self.tau_max = min(self.tau_max_requested, windows - 1)
        self.user_cap = user_cap
        self.subset = SubsetSpec.resolve(subset, params0)
        # subset mode: snapshots store only the personal leaves; the shared
        # backbone lives once here and is updated by reference each advance
        # (subset applies never change it, so it is valid for EVERY window)
        self._base = params0
        self.row_nbytes: Optional[int] = None  # set at first retained bank
        # fp32-equivalent row bytes (== row_nbytes for fp32 banking): the
        # baseline the quant residency gate and bytes-saved stat compare to
        self.row_nbytes_fp32: Optional[int] = None
        self.current = 0
        self._snapshots: Dict[int, object] = {0: self._store(params0)}
        self._banks: Dict[int, List[DeltaBank]] = {0: []}
        # (bank, row, τ) admitted to the window currently accumulating
        self._pending: List[Tuple[DeltaBank, int, int]] = []
        # user -> rows admitted to the accumulating window (fairness cap)
        self._user_rows: Dict[object, int] = {}
        # user -> (window, bank, row): the user's latest served delta row
        self._by_user: Dict[object, Tuple[int, DeltaBank, int]] = {}
        self.stats = {"windows": 0, "admitted": 0, "stragglers": 0,
                      "dropped": 0, "fairness_capped": 0,
                      "robust_clipped": 0, "robust_trimmed": 0,
                      "robust_nonfinite": 0}

    # -- retention ---------------------------------------------------------

    def _store(self, params):
        """What a window snapshot physically retains: the personal subset
        only (pruned tree) in subset mode, the full params otherwise."""
        return self.subset.extract(params) if self.subset is not None \
            else params

    def _thaw(self, snap):
        """Demoted (int8) snapshots dequantize transparently on access."""
        return dequantize_tree(snap) if isinstance(snap, QuantTree) else snap

    def snapshot(self, window: int):
        """FULL params the given window's cohorts were computed against
        (subset snapshots recombine with the shared backbone on demand;
        int8-demoted snapshots of older windows dequantize on the fly)."""
        snap = self._thaw(self._snapshots[window])
        if self.subset is not None:
            return merge_subset(self._base, snap)
        return snap

    def subset_snapshot(self, window: int):
        """The window's snapshot in stored *structure* — the pruned subset
        tree in subset mode (what head computation subtracts subset delta
        stacks from), the full params otherwise — dequantized to fp32 when
        the window was demoted to int8."""
        return self._thaw(self._snapshots[window])

    def retain(self, bank) -> None:
        """Bank-handoff hook: pin ``bank`` to the current window so its
        device buffer outlives the window (stragglers, head gathers).
        ``bank`` is a DeltaBank or, under int8 banking, a
        :class:`repro.core.quant.QuantizedBank` — the ring only needs
        ``stacked``/``capacity``/``__len__``."""
        self._banks[self.current].append(bank)
        if self.row_nbytes is None and len(bank):
            self.row_nbytes = _row_nbytes(bank.stacked)
            self.row_nbytes_fp32 = (
                fp32_row_nbytes(bank.stacked)
                if isinstance(bank.stacked, QuantStack)
                else self.row_nbytes)

    def lookup(self, user):
        """-> (window, bank, row) of the user's latest admitted delta, or
        None once the row's window has retired from the ring."""
        return self._by_user.get(user)

    def admitted_rows(self, user) -> int:
        """Rows this user already has admitted into the accumulating
        window — the consumed share of the ``user_cap`` fairness budget
        (front-ends consult this to refuse over-cap work at the door)."""
        return self._user_rows.get(user, 0)

    @property
    def live_banks(self) -> int:
        return sum(len(b) for b in self._banks.values())

    # -- admission ---------------------------------------------------------

    def admit_row(self, user, bank: DeltaBank, row: int, tau: int) -> str:
        """Admit one delta row into the accumulating window's apply.

        ``tau`` is the row's staleness in windows (0 = computed against the
        current snapshot).  Straggler rows (τ > 0) are re-weighted into
        THIS window — the "next" window relative to the one they were
        stamped in.  The ring is the admission authority, so a refusal
        *reports its cause*: ``"dropped"`` for rows past ``tau_max``,
        ``"capped"`` for a user's row past the per-window fairness cap
        (``user_cap``), ``"admitted"`` otherwise — callers surface the
        cause to the user (a fairness refusal is re-submittable next
        window; a staleness drop needs a fresh snapshot).
        """
        if tau > self.tau_max:
            self.stats["dropped"] += 1
            return "dropped"
        if self.user_cap is not None \
                and self._user_rows.get(user, 0) >= self.user_cap:
            self.stats["fairness_capped"] += 1
            return "capped"
        if tau > 0:
            self.stats["stragglers"] += 1
        self.stats["admitted"] += 1
        self._user_rows[user] = self._user_rows.get(user, 0) + 1
        self._pending.append((bank, row, tau))
        self._by_user[user] = (self.current, bank, row)
        return "admitted"

    def admit(self, user, bank: DeltaBank, row: int, tau: int) -> bool:
        """Boolean convenience wrapper over :meth:`admit_row`."""
        return self.admit_row(user, bank, row, tau) == "admitted"

    # -- window boundary ---------------------------------------------------

    def advance(self, state: ServerState, *, beta: float,
                damping: float = 0.0) -> ServerState:
        """Close the accumulating window: apply every admitted row to the
        server state and rotate the ring.

        One fused ``apply_rows`` pass per contributing bank — weights fold
        β/M, per-row staleness damping and bucket-padding masks, exactly
        the buffered scheduler's math (:func:`admission_weights` is shared
        with it).  Each bank's apply receives the window's ADMISSION order
        (the order rows entered ``admit_row`` — submit order, by the
        batcher's contract): on device-spanning banks the rows accumulate
        sequentially in that order, so the post-advance params are
        bit-identical between the 1-D and 2-D mesh layouts even though
        the user→row placement differs.  Returns the post-apply state;
        the pre-apply params become the closed window's snapshot and stay
        retained (the apply never donates them).
        """
        m = len(self._pending)
        if m:
            with span("ring.apply", window=self.current, rows=m):
                state = self._apply(state, beta=beta, damping=damping)
        self._pending = []
        self._user_rows = {}
        self.stats["windows"] += 1
        self.current += 1
        self._base = state.params
        self._snapshots[self.current] = self._store(state.params)
        self._banks[self.current] = []
        if self.delta_dtype == "int8":
            # demote the just-closed window's snapshot (personal leaves) to
            # int8: only stragglers re-read it, and their banked deltas are
            # int8+EF anyway.  The CURRENT snapshot stays exact fp32 so
            # fresh heads carry no quantization noise.
            prev = self.current - 1
            if prev in self._snapshots \
                    and not isinstance(self._snapshots[prev], QuantTree):
                with span("ring.demote", window=prev):
                    self._snapshots[prev] = quantize_tree(
                        self._snapshots[prev])
        horizon = self.current - self.windows + 1
        for w in [w for w in self._snapshots if w < horizon]:
            del self._snapshots[w]
            self._banks.pop(w, None)
        for user in [u for u, (w, _, _) in self._by_user.items()
                     if w < horizon]:
            del self._by_user[user]
        return state

    def _apply(self, state: ServerState, *, beta: float,
               damping: float) -> ServerState:
        """One fused apply per contributing bank of the pending rows."""
        m = len(self._pending)
        groups: Dict[int, Tuple[DeltaBank, List[Tuple[int, int]]]] = {}
        for bank, row, tau in self._pending:
            groups.setdefault(id(bank), (bank, []))[1].append((row, tau))
        if self.robust is not None:
            # one call for the whole window — the defense calibrates
            # over every pending admission, current bank and straggler
            # banks together (a lone straggler row would otherwise set
            # its own clip median); row norms are reduced on device
            # ([capacity] f32 is all that crosses to host)
            per_bank, info = robust_flush_weights(
                groups, beta=beta, count=m, damping=damping,
                tau_max=self.tau_max, method=self.robust,
                clip_norm=self.clip_norm, trim_frac=self.trim_frac)
            for key in ("clipped", "trimmed", "nonfinite"):
                self.stats[f"robust_{key}"] += info[key]
        for key, (bank, rows) in groups.items():
            if self.robust is not None:
                weights, keep = per_bank[key]
                # non-finite rows masked out of the stack so
                # 0-weights cannot leak NaNs (0×NaN=NaN)
                stack = bank.stacked if bool(keep.all()) \
                    else mask_rows(bank.stacked, keep)
            else:
                weights = admission_weights(
                    bank.capacity, rows, beta=beta, count=m,
                    damping=damping, tau_max=self.tau_max)
                stack = bank.stacked
            # admission order, deduped (a twice-admitted row already
            # carries its accumulated weight), then the zero-weight
            # remainder — a full permutation for the ordered apply
            seen, order = set(), []
            for r, _ in rows:
                if r not in seen:
                    seen.add(r)
                    order.append(r)
            order.extend(r for r in range(bank.capacity)
                         if r not in seen)
            state = apply_admitted_rows(
                state, stack, weights, len(rows),
                staleness_max=max(t for _, t in rows),
                staleness_sum=float(sum(t for _, t in rows)),
                order=order)
        return state

    # -- restart warm-start ------------------------------------------------

    def load(self, snapshots: Dict[int, object], current: int,
             stats: Optional[Dict[str, int]] = None) -> None:
        """Warm-start after a process restart: install the checkpointed
        params snapshots, window counter AND cumulative ``stats`` (see
        ``PersonalizationServer.save``/``restore``).  Banks, pending
        admissions and per-user delta rows start empty — in-flight
        straggler rows do not survive a restart — but straggler *requests*
        stamped before the crash can still drain against their restored
        snapshots.

        Persistence scope of the counters: every key of ``self.stats``
        (``windows``/``admitted``/``stragglers``/``dropped``/
        ``fairness_capped``) is lifetime-cumulative and survives restarts
        through the checkpoint — per-window serve metrics derived from
        them (e.g. admitted-per-window) stay consistent with the restored
        window counter instead of restarting at zero.  Engine and batcher
        stats (``host_materializations``, ``cohort_calls``, …) are
        *process-local* by design and always restart at zero.  Pre-stats
        checkpoints fall back to ``windows = current`` (the one counter
        the window id implies) with the rest unknown-as-zero."""
        if current not in snapshots:
            raise ValueError(f"current window {current} has no snapshot")
        horizon = current - self.windows + 1
        self.current = current
        self._snapshots = {w: s for w, s in snapshots.items()
                           if w >= horizon}
        self._banks = {w: [] for w in self._snapshots}
        self._pending = []
        self._user_rows = {}
        self._by_user = {}
        if stats is not None:
            self.stats.update({k: int(v) for k, v in stats.items()
                               if k in self.stats})
        else:
            self.stats["windows"] = current
