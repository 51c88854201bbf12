"""Request queue + micro-batcher for personalization traffic.

Batcher *modes* are the paper's personalization options (see the package
docstring): mode ``"B"`` is the Per-FedAvg one-step MAML fine-tune, mode
``"C"`` the pFedMe Moreau-envelope prox solve.  Each mode owns a
:class:`repro.fl.engine.CohortEngine` driven by the registry strategy
``repro.fl.api.strategy("personalize", mode=...)``, whose ``local_update``
computes the *personalization delta* — a params-shaped pytree with
``head = w − delta`` — so concurrent users ride the exact vmap / lax.map /
shard_map machinery (pow2 buckets, on-device DeltaBank) the training
cohorts use, and the resulting bank rows double as the server-side update
direction the ring folds back into the global model.  (The pre-PR-4
``CohortEngine(client_fn=...)`` override and its ``personalize_delta_fn``
helper were removed in PR 10.)

Fairness: ``user_cap`` bounds how many of one user's rows are admitted per
aggregation window, so users with unequal request rates cannot monopolize
the window's ``apply_rows`` weight vector — over-cap requests are refused
*before* spending a cohort slot (``status="capped"``; re-submit next
window) and counted in ``stats["fairness_capped"]``.

Under ``cohort_impl="shard_map"`` the batcher lays the cohort out
*cohort-slice-major*: user ``u`` always occupies a slot in cohort slice
``crc32(u) % n_slices``, where ``n_slices`` is the mesh's COHORT-axis
size — all of a 1-D ``("cohort",)`` mesh's devices, or the rows of a 2-D
``("cohort", "model")`` mesh, on which one slice is a whole
model-parallel device group.  The user's delta row therefore lands on the
same slice every window (stable row affinity — the "keyed by user slice"
part of the ring-buffer), with its model dims spread over that slice's
"model" devices.  Per-slice slots pad to a common pow2, which is exactly
the engine's slice-multiple bucket, so the layout adds no padding beyond
what the engine would.  The ``placed`` list a drain yields stays in
SUBMIT order — the mesh-independent admission order the ring passes to
the ordered window apply, which is what keeps post-advance params
bit-identical across mesh layouts.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Callable, Dict, Iterator, List, Optional, Tuple

from repro.core.types import PersAFLConfig
from repro.fl.api import strategy as _strategy
from repro.fl.engine import CohortEngine, DeltaBank
from repro.spans import span

MODES = ("B", "C")


def personalize_strategy(pcfg: PersAFLConfig, loss_fn: Callable, mode: str,
                         personal_subset=None):
    """The bound ``strategy("personalize", mode=...)`` behind one batcher
    mode — the registry rule whose ``local_update`` maps
    ``(params, batch)`` to the personalization delta (head = w − delta).
    With ``personal_subset`` set, the delta covers only the personal
    leaves (pruned subset structure; backbone frozen) and every bank row
    downstream shrinks accordingly."""
    return _strategy("personalize", mode=mode,
                     personal_subset=personal_subset).bind(pcfg, loss_fn)


def __getattr__(name: str):
    if name == "personalize_delta_fn":
        raise ImportError(
            "repro.serving.batcher.personalize_delta_fn was removed in "
            "PR 10 (deprecated since PR 4); use repro.fl.api.strategy("
            "'personalize', mode=...) / personalize_strategy instead.")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


@dataclasses.dataclass
class Ticket:
    """Submit/poll handle for one personalization request.

    A "done" ticket carries its OWN result handle — ``head`` is the
    (heads DeltaBank, row) pair its flush produced and ``window`` the ring
    window it was served in — so polling an older ticket after a newer
    flush returns *that ticket's* head, never silently the newest one
    (resolving by user aliased them).  Once ``window`` retires from the
    ring the ticket is superseded-and-retired and polls fail explicitly.
    """
    user: object
    mode: str
    stamp: int                 # ring window the request was submitted in
    status: str = "queued"     # queued | done | dropped | capped
    tau: int = 0               # staleness in windows, set at drain time
    window: int = -1           # ring window the ticket was SERVED in
    head: Optional[tuple] = dataclasses.field(  # (heads bank, row)
        default=None, repr=False, compare=False)


def _pow2(k: int) -> int:
    return 1 << max(k - 1, 0).bit_length()


class MicroBatcher:
    """Coalesces concurrent personalization requests into cohort calls.

    Requests queue until :meth:`drain`, which groups them by
    ``(mode, stamp)`` — every group shares one params snapshot, the
    precondition for a single cohort call — and emits one pow2-bucketed
    ``update_cohort`` per group.  Straggler groups (stamp < current window)
    are computed against their *stamped* snapshot, so the delta the ring
    re-weights into the current window is the delta the user's own device
    would have uploaded.
    """

    def __init__(self, engines: Dict[str, CohortEngine],
                 n_shards: int = 1, user_cap: Optional[int] = None):
        self.engines = engines
        self.n_shards = max(int(n_shards), 1)
        self.user_cap = user_cap
        self._queue: List[Tuple[Ticket, Dict]] = []
        # per-user rows admitted to the window currently accumulating
        self._cap_window: int = -1
        self._user_rows: Dict[object, int] = {}
        self.stats = {"submitted": 0, "drains": 0, "cohort_calls": 0,
                      "max_coalesced": 0, "dropped": 0,
                      "fairness_capped": 0}

    def __len__(self) -> int:
        return len(self._queue)

    def submit(self, ticket: Ticket, batch) -> Ticket:
        if ticket.mode not in self.engines:
            raise ValueError(f"mode {ticket.mode!r} not enabled; "
                             f"have {sorted(self.engines)}")
        self.stats["submitted"] += 1
        self._queue.append((ticket, batch))
        return ticket

    def _shard(self, user) -> int:
        return zlib.crc32(str(user).encode()) % self.n_shards

    def _layout(self, reqs: List[Tuple[Ticket, Dict]]):
        """Slice-major cohort layout -> (batch_list, [(ticket, row)],
        filler rows).

        With one cohort slice the engine's own tail padding suffices; with
        N the per-slice slot count pads to a pow2 so the total is exactly
        the engine's slice-multiple bucket (row i ↦ cohort slice
        i // per_slice).  ``placed`` is emitted in SUBMIT order regardless
        of which slice each request landed on: admission order must be a
        mesh-independent total order on the window's rows (the ring feeds
        it to the ordered window apply), and slice-major order would
        permute with the mesh shape.  The filler rows are the engine's
        padding as much as its own bucket tail is, and are counted with it
        (``padding_waste``).
        """
        if self.n_shards == 1:
            return ([b for _, b in reqs],
                    [(t, i) for i, (t, _) in enumerate(reqs)], 0)
        shards: List[List[Tuple[int, Dict]]] = \
            [[] for _ in range(self.n_shards)]
        for qi, (t, _) in enumerate(reqs):
            shards[self._shard(t.user)].append((qi, reqs[qi][1]))
        per = _pow2(max(max(len(s) for s in shards), 1))
        fill = reqs[-1][1]
        batch_list, row_of = [], {}
        for si, s in enumerate(shards):
            for j in range(per):
                if j < len(s):
                    qi, b = s[j]
                    batch_list.append(b)
                    row_of[qi] = si * per + j
                else:
                    batch_list.append(fill)
        return (batch_list,
                [(t, row_of[qi]) for qi, (t, _) in enumerate(reqs)],
                len(batch_list) - len(reqs))

    def drain(self, current: int, snapshot_fn: Callable[[int], object], *,
              tau_max: int) -> Iterator[Tuple[str, int, DeltaBank,
                                              List[Tuple[Ticket, int]]]]:
        """Yield ``(mode, stamp, bank, [(ticket, row), ...])`` per group.

        Requests whose staleness ``current − stamp`` exceeds ``tau_max``
        (or whose snapshot already retired from the ring) are marked
        ``dropped`` without spending a cohort slot on them; with
        ``user_cap`` set, a user's requests beyond the cap *within one
        aggregation window* are likewise refused pre-cohort
        (``status="capped"``) so heavy users cannot monopolize the
        window's apply weight vector.
        """
        queue, self._queue = self._queue, []
        if not queue:
            return
        groups = None
        while groups is None or groups:
            # one span per group; the first also covers the grouping
            with span("batcher.drain", window=current):
                if groups is None:
                    groups = self._group(queue, current, tau_max)
                    if not groups:
                        return
                (mode, stamp), reqs = groups.pop(0)
                batch_list, placed, filler = self._layout(reqs)
                self.stats["cohort_calls"] += 1
                engine = self.engines[mode]
                engine.stats["padding_waste"] += filler
                bank = engine.update_cohort(snapshot_fn(stamp), batch_list)
            yield mode, stamp, bank, placed

    def _group(self, queue, current: int, tau_max: int):
        """Admission-filtered requests grouped by ``(mode, stamp)``,
        oldest stamp first."""
        if current != self._cap_window:        # window rolled: caps reset
            self._cap_window = current
            self._user_rows = {}
        self.stats["drains"] += 1
        self.stats["max_coalesced"] = max(self.stats["max_coalesced"],
                                          len(queue))
        groups: Dict[Tuple[str, int], List[Tuple[Ticket, Dict]]] = {}
        for ticket, batch in queue:
            ticket.tau = current - ticket.stamp
            if ticket.tau > tau_max:
                ticket.status = "dropped"
                self.stats["dropped"] += 1
                continue
            if self.user_cap is not None:
                used = self._user_rows.get(ticket.user, 0)
                if used >= self.user_cap:
                    ticket.status = "capped"
                    self.stats["fairness_capped"] += 1
                    continue
                self._user_rows[ticket.user] = used + 1
            groups.setdefault((ticket.mode, ticket.stamp), []).append(
                (ticket, batch))
        return sorted(groups.items(), key=lambda kv: kv[0][1])
