"""Live policy plane: learned-mode vetoes and shadow A/B scoring
(ISSUE 18 tentpole d).

One `PolicyPlane` per Server when `--sys.policy.file` names a trained
artifact (policy/train.py); default **off** — `Server.policy is None`,
every hook site pays one `is None` check (the r7 skip-wrapper
discipline), and the registry holds zero `policy.*` names (pinned by
`scripts/metrics_overhead_check.py`; `policy` is an adapm-lint
OPTIONAL_HANDLE).

Per decision plane, `--sys.policy.<plane>` selects:

  `heuristic`  (default) the hand-tuned law decides, exactly as before.
               With `--sys.policy.shadow 1` the learned model is ALSO
               scored at each decision — `policy.shadow_agree` /
               `policy.shadow_disagree` count whether it would have
               done the same — but its verdict is never applied (the
               observer-effect pin: shadow on/off replays produce
               identical reads digests).
  `learned`    the model's regret prediction may VETO the heuristic's
               action (hold a background promotion, skip a landed
               move, dirty-filter a ship, keep the serve window).
               The veto is the ONLY power the policy has — it never
               proposes an action the heuristic would not take — and
               each hook site applies it through a value-preservation
               guard (see the site comments in core/kv.py,
               tier/promote.py, core/sync.py, obs/slo.py): a policy
               changes *what/when*, never *values*, so any
               value-preserving replay reproduces the heuristic
               `reads_digest` bitwise. `policy.guard_vetoes_total`
               counts verdicts the guard refused to apply.

Promotion gate: `learned` is only worth turning on after
`replay.rank_candidates` over {heuristic, learned} ranks learned at or
above the heuristic on the plane's regret objective
(docs/POLICY.md; scripts/policy_gate_check.py enforces it for tier in
CI).

Thread safety: hook sites consult concurrently; per-plane tallies are
folded under one small lock (counter bumps + dict increments only —
never a device wait, never the server lock).
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

from .features import core_features
from .model import PolicyBundle, load_policy

PLANE_KNOBS = ("reloc", "tier", "sync", "serve")
POLICY_MODES = ("heuristic", "learned")


class PolicyPlane:
    """Owned and built by the Server (core/kv.py) when
    `--sys.policy.file` is set; stateless between consults apart from
    tallies — the models themselves are immutable after load."""

    def __init__(self, server, opts=None):
        from ..obs.metrics import Counter
        o = opts if opts is not None else server.opts
        self._server = server
        self.modes: Dict[str, str] = {
            "reloc": o.policy_reloc, "tier": o.policy_tier,
            "sync": o.policy_sync, "serve": o.policy_serve}
        self.shadow = bool(o.policy_shadow)
        self.file = o.policy_file
        self.bundle: PolicyBundle = load_policy(o.policy_file)
        # planes worth paying the feature read for: learned mode, or
        # shadow scoring — in both cases only when the artifact
        # actually shipped a model for the plane
        self._active = frozenset(
            p for p in PLANE_KNOBS if p in self.bundle.planes and
            (self.modes[p] == "learned" or self.shadow))
        self._lock = threading.Lock()
        z = {"consults": 0, "vetoes": 0, "applied": 0,
             "guard_blocked": 0, "agree": 0, "disagree": 0}
        self._tallies = {p: dict(z) for p in PLANE_KNOBS}
        # serve batch-window observations (serve/batcher.py): how the
        # live windows actually close — the denominator a shadow A/B
        # reads the serve model against (docs/POLICY.md runbook)
        self._batch_window_limited = 0
        self._batch_size_limited = 0
        reg = server.obs
        if reg is not None and reg.enabled:
            self.c_consults = reg.counter("policy.consults_total")
            self.c_applied = reg.counter("policy.applied_total")
            self.c_guard = reg.counter("policy.guard_vetoes_total")
            self.c_agree = reg.counter("policy.shadow_agree")
            self.c_disagree = reg.counter("policy.shadow_disagree")
        else:  # works with --sys.metrics 0 (standalone tallies)
            self.c_consults = Counter("policy.consults_total")
            self.c_applied = Counter("policy.applied_total")
            self.c_guard = Counter("policy.guard_vetoes_total")
            self.c_agree = Counter("policy.shadow_agree")
            self.c_disagree = Counter("policy.shadow_disagree")

    # -- hook-site API -------------------------------------------------------

    def active(self, plane: str) -> bool:
        """Cheap pre-check for hook sites: is there anything to score
        here? False for heuristic-mode planes with shadow off — the
        site then skips even building its extras dict."""
        return plane in self._active

    def consult(self, plane: str, extras: Dict, batch_n: int) -> bool:
        """Score the plane's model on the live features. In `learned`
        mode returns the veto verdict (True = hold the heuristic's
        action, subject to the SITE's value-preservation guard). In
        shadow mode the verdict only feeds the agree/disagree counters
        — the heuristic's action (always: proceed) is applied, so the
        return is False by construction."""
        if plane not in self._active:
            return False
        m = self.bundle.planes[plane]
        f = core_features(self._server, batch_n)
        f.update(extras)
        verdict = m.veto(f)
        learned = self.modes[plane] == "learned"
        with self._lock:
            # the total and the plane's tally move together: `stats()`
            # reads both under this lock (the tier worker consults while
            # a snapshot is taken)
            self.c_consults.inc()
            t = self._tallies[plane]
            t["consults"] += 1
            if learned:
                if verdict:
                    t["vetoes"] += 1
            elif verdict:
                t["disagree"] += 1
            else:
                t["agree"] += 1
        if not learned:  # shadow: scored, never applied
            (self.c_disagree if verdict else self.c_agree).inc()
            return False
        return verdict

    def applied(self, plane: str) -> None:
        """The site's value-preservation guard admitted the veto and
        the heuristic's action was held."""
        self.c_applied.inc()
        with self._lock:
            self._tallies[plane]["applied"] += 1

    def guard_blocked(self, plane: str) -> None:
        """The guard refused the veto (applying it could have changed
        read values) — the heuristic's action proceeded."""
        self.c_guard.inc()
        with self._lock:
            self._tallies[plane]["guard_blocked"] += 1

    def note_batch(self, window_limited: bool) -> None:
        """serve/batcher.py per-batch close reason: the window expired
        (coalescing lever bound) vs the batch filled first."""
        with self._lock:
            if window_limited:
                self._batch_window_limited += 1
            else:
                self._batch_size_limited += 1

    # -- snapshot ------------------------------------------------------------

    def stats(self) -> Dict:
        """Plain-value summary for `metrics_snapshot()["policy"]` (the
        registry-backed policy.* counters land in the same section)."""
        with self._lock:
            out: Dict = {"file": self.file, "shadow": self.shadow,
                         "planes_loaded":
                             sorted(self.bundle.planes),
                         "batch_window_limited":
                             self._batch_window_limited,
                         "batch_size_limited":
                             self._batch_size_limited,
                         # over the registry's reading of the same
                         # counter, taken at another moment
                         "consults_total": self.c_consults.snap()}
            for p in PLANE_KNOBS:
                out[f"mode.{p}"] = self.modes[p]
                t = self._tallies[p]
                out[f"consults.{p}"] = t["consults"]
                out[f"vetoes.{p}"] = t["vetoes"]
                out[f"applied.{p}"] = t["applied"]
                out[f"guard_blocked.{p}"] = t["guard_blocked"]
                out[f"shadow_agree.{p}"] = t["agree"]
                out[f"shadow_disagree.{p}"] = t["disagree"]
        return out
