"""Serving cells: ``MoleculeOptService``, the continuously-batched request
router, serving the trained general model to an open loop of requests.

Set-up builds one service from the seed (the Q-network and both
predictors drawn on the device in one jitted call), warms the serving Q
dispatch at the reserved candidate capacity and every padded predictor
batch up to the slot count, and primes it with ``warm_requests`` requests
from the warm starts, run until the service is idle.

The window is an open loop on the wall clock, seen from the client.  The
arrival schedule is drawn before the window opens; before each service
step every request whose scheduled time has passed is submitted; each
request is recorded as (scheduled arrival, completion, terminal status),
its completion being the end of the service step that finalised it, so
the time a request waits before it is submitted counts.  The window
record (``ctx["w"]["records"]``) is all that the end-to-end readers read.

* ``"end": "drain"``: arrivals stop at ``--seconds`` and the window ends
  when the last arrival is terminal.  A request still open
  ``drain_grace_s`` after the arrivals stopped is recorded ``unfinished``
  and counts as failed.
* ``"end": "window"``: the window ends at the first step boundary past
  ``--seconds``; requests still queued or in flight are neither completed
  nor failed.

A seeded sample of the window's service steps is captured for the check:
the candidate rows the Q dispatch was given and the Q values it returned,
the candidates and steps left of each slot, the greedy choices, and the
molecules the predictors computed afresh in those steps.  The rows are
the program's own arrays, held, not copied: the capture costs the window
no more than a few list appends.

Traffic parameters (``workloads/<cell>.json``, key ``traffic``):

    knee_rps, load      arrivals per second are ``knee_rps * load``; the knee
                        is the highest rate at which the queue stays bounded
                        (``knee.py``)
    end                 "drain" or "window" (above)
    starts              [lo, hi) of ``antioxidant_dataset(dataset_size)``: the
                        timed starts, drawn uniformly without repeats until
                        all are used
    budget              [lo, hi]: uniform integer step budgets

and, with the values of ``DEFAULTS`` unless the file sets them:

    arrival_stream      the seed of the arrival times and of the multisets of
                        budgets and starts: every ``--seed`` gets the same
                        Poisson schedule, budgets and starts, and draws which
                        start and which budget each arrival carries
    drain_grace_s       how long a drained window waits past its arrivals
    warm_starts         [lo, hi) of the dataset: the priming requests' starts
    warm_requests       requests that prime the service in set-up
    max_queue           the admission queue's bound
    reserve_candidates  the candidate capacity warmed in set-up
    sample_steps, sample_from   the check's sample: ``sample_steps`` of the
                        window's first ``sample_from`` service steps
    control_seconds     the window ``control.py`` drives before its readings
"""

from __future__ import annotations

import time

import numpy as np

from .. import control, correct, harness
from . import common


DEFAULTS = {"arrival_stream": 17, "drain_grace_s": 90,
            "warm_starts": [0, 600], "warm_requests": 128, "max_queue": 4096,
            "reserve_candidates": 512, "sample_steps": 8, "sample_from": 32,
            "control_seconds": 20}


class _RecordingService:
    """The property service as the router sees it; while ``record`` is a
    list, each call appends the molecules it computed afresh (not answered
    from the service's cache, first of each key) with their answers."""

    def __init__(self, inner):
        self.inner = inner
        self.record: list | None = None

    def predict(self, mols):
        if self.record is None:
            return self.inner.predict(mols)
        cache = self.inner.cache
        seen, fresh = set(), []
        for i, m in enumerate(mols):
            k = m.iso_key()
            if k not in seen and (cache is None or k not in cache):
                fresh.append(i)
            seen.add(k)
        out = self.inner.predict(mols)
        self.record.append(([mols[i] for i in fresh], [out[i] for i in fresh]))
        return out

    def __getattr__(self, name):
        return getattr(self.inner, name)


class ServeRun:
    check = staticmethod(correct.check_serve)
    readings = staticmethod(control.serve_readings)

    def __init__(self, cfg: dict, wl: dict, seed: int, spans: harness.Spans):
        self.cfg, self.wl, self.seed, self.spans = cfg, wl, seed, spans
        self.traffic = {**DEFAULTS, **wl["traffic"]}
        self.pseed = harness.program_seed(seed)
        self.control_seconds = self.traffic["control_seconds"]
        self.capture: dict = {"steps": [], "predictions": []}
        self.bound = 0             # slots stepped, summed over service steps
        # the steps left that each request's rows should carry, from the
        # budget the client sent (at most ``max_steps``) and the dispatches
        # the benchmark counted
        self.budgets: dict[str, int] = {}
        self.taken: dict[str, int] = {}
        self._step: dict | None = None
        rng = np.random.default_rng([int(seed), 0x5E7E])
        t = self.traffic
        self._sample = set(rng.choice(t["sample_from"], t["sample_steps"],
                                      replace=False).tolist())

    # -------------------------------------------------------------- #
    def build(self) -> None:
        import jax

        from repro.chem.smiles import canonical_smiles
        from repro.core.agent import QNetwork
        from repro.data.datasets import antioxidant_dataset
        from repro.predictors.service import PropertyService
        from repro.serving import MoleculeOptService, ServeConfig

        cfg, t = self.cfg, self.traffic
        s = cfg["serve"]
        bde_m, ip_m = common.predictor_models(cfg)
        net = QNetwork(hidden=tuple(cfg["qnet"]["hidden"]))

        def draw(kq, kb, ki):
            return {"q": net.init(kq), "bde": bde_m.init(kb), "ip": ip_m.init(ki)}
        w = jax.jit(draw)(*common.weight_keys(self.pseed))
        jax.block_until_ready(w)
        self.inner_service = PropertyService(bde_m, w["bde"], ip_m, w["ip"],
                                             max_atoms=cfg["predictors"]["max_atoms"])
        self.service = _RecordingService(self.inner_service)
        self.svc = svc = MoleculeOptService(
            net, w["q"], self.service,
            cfg=ServeConfig(n_slots=s["n_slots"], max_queue=t["max_queue"],
                            max_steps=s["max_steps"], epsilon=s["epsilon"],
                            chem=s["chem"], seed=self.pseed))
        ds = antioxidant_dataset(cfg["data"]["dataset_size"])
        self.warm_mols = ds[slice(*t["warm_starts"])]
        self.starts = [canonical_smiles(m) for m in ds[slice(*t["starts"])]]
        self.warm_smiles = [canonical_smiles(m)
                            for m in self.warm_mols[:t["warm_requests"]]]
        policy = svc._policy
        self._dispatch = policy.fleet_q_values
        policy.fleet_q_values = self._q_values
        self._select = policy.select_action
        policy.select_action = self._select_action

    # ---- hooks ---------------------------------------------------- #
    def _q_values(self, per_worker):
        q = self._dispatch(per_worker)
        self.bound += sum(1 for x in per_worker if x.shape[0])
        svc = self.svc
        rids = [svc._active[w].req.request_id if w in svc._active and x.shape[0]
                else None for w, x in enumerate(per_worker)]
        if self._step is not None:
            live = [[s for s in slots if s.steps_left > 0]
                    for slots in svc.engine.workers]
            self._step.update(
                x=list(per_worker), q=list(q),
                cands=[[a for s in ss for a in s.candidates] for ss in live],
                slots=[[(self.budgets[r] - self.taken.get(r, 0), len(s.candidates))
                        for s in ss] for ss, r in zip(live, rids)])
        for r in rids:
            if r is not None:
                self.taken[r] = self.taken.get(r, 0) + 1
        return q

    def _select_action(self, q, worker: int) -> int:
        a = self._select(q, worker)
        if self._step is not None:
            self._step["choices"].append((worker, a))
        return a

    # -------------------------------------------------------------- #
    def warm(self) -> None:
        """Every shape the window uses, then the priming requests."""
        from repro.serving import OptimizeRequest

        svc, t = self.svc, self.traffic
        svc.reserve_candidates(t["reserve_candidates"])
        self.shapes = 1 + common.warm_predictor_shapes(self.inner_service,
                                                       self.warm_mols)
        for i, smi in enumerate(self.warm_smiles):
            self.budgets[f"warm{i}"] = self.cfg["serve"]["max_steps"]
            svc.submit(OptimizeRequest(request_id=f"warm{i}", smiles=smi,
                                       objective=self.cfg["serve"]["scenario"],
                                       budget=self.cfg["serve"]["max_steps"],
                                       seed=i))
        svc.run_until_idle()
        bad = {k: v for k, v in svc.status_counts.items()
               if k != "completed" and v}
        if bad:
            raise harness.BenchError(f"priming requests ended {bad}")
        self.cap = svc._policy._cap

    def schedule(self, seconds: float) -> tuple[np.ndarray, list]:
        """Arrival times in ``[0, seconds)`` and their requests."""
        from repro.serving import OptimizeRequest

        t = self.traffic
        rate = t["knee_rps"] * t["load"]
        fixed = np.random.default_rng(t["arrival_stream"])
        n_max = int(rate * seconds * 2 + 64)
        times = np.cumsum(fixed.exponential(1.0 / rate, n_max))
        times = times[times < seconds]
        lo, hi = t["budget"]
        budgets = fixed.integers(lo, hi + 1, len(times))
        chosen = np.resize(fixed.permutation(len(self.starts)), len(times))
        rng = np.random.default_rng([int(self.seed), 0xA881])
        budgets = rng.permutation(budgets)
        order = rng.permutation(chosen)
        reqs = [OptimizeRequest(request_id=f"r{i}", smiles=self.starts[order[i]],
                                objective=self.cfg["serve"]["scenario"],
                                budget=int(budgets[i]), seed=i)
                for i in range(len(times))]
        cap = self.cfg["serve"]["max_steps"]
        self.budgets.update((r.request_id, min(r.budget, cap)) for r in reqs)
        return times, reqs

    def counters(self) -> dict:
        return {"service_steps": self.svc.n_service_steps, "bound": self.bound}

    def window(self, seconds: float) -> dict:
        """The open loop (module docstring)."""
        svc, t = self.svc, self.traffic
        times, reqs = self.schedule(seconds)
        drain = t["end"] == "drain"
        sched = {r.request_id: float(a) for a, r in zip(times, reqs)}
        done: dict[str, tuple[float, str]] = {}
        i, k, n = 0, 0, len(reqs)
        late = 0.0                 # how far submissions ran behind schedule
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while i < n and times[i] <= now:
                late = max(late, now - times[i])
                if svc.submit(reqs[i]) != "queued":
                    done[reqs[i].request_id] = (now, svc.results[-1].status)
                i += 1
            if not drain and now >= seconds:
                break
            if drain and now >= seconds + t["drain_grace_s"]:
                break
            if svc.idle:
                if i >= n:
                    if drain:
                        break
                    time.sleep(max(0.0, seconds - now))
                else:
                    time.sleep(max(0.0, times[i] - now))
                continue
            self._step = {"choices": []} if k in self._sample else None
            if self._step is not None:
                self.service.record = []
            finished = svc.step()
            end = time.perf_counter() - t0
            for r in finished:
                done[r.request_id] = (end, r.status)
            if self._step is not None:
                self.capture["steps"].append(self._step)
                self.capture["predictions"] += self.service.record
                self.service.record = None
                self._step = None
            k += 1
        window_s = time.perf_counter() - t0
        records = [(sched[rid], *done[rid]) for rid in sched if rid in done]
        if drain:
            records += [(sched[rid], window_s, "unfinished")
                        for rid in sched if rid not in done]
        bad = ("failed", "unfinished") + (("shed", "deadline_exceeded") if drain else ())
        failed = sum(1 for r in records if r[2] in bad)
        harness.log(f"[window] {n} arrivals at {t['knee_rps'] * t['load']:.3f}/s, "
                    f"{k} service steps, {len(records)} terminal, "
                    f"{sum(1 for r in records if r[2] == 'completed')} completed, "
                    f"{len(svc.queue)} queued and {len(svc._active)} bound at the end, "
                    f"{len(self.capture['steps'])} steps captured, submissions "
                    f"at most {late:.3f} s behind schedule")
        return {"window_s": window_s, "attempted": len(records), "failed": failed,
                "records": records, "arrivals": n}

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        import gc

        self.svc = None
        self.inner_service = None
        self.service = None
        self._dispatch = self._select = None
        gc.collect()


Run = ServeRun
