"""A run of a cell with several kv shards with a fault planted under the
timed path, beside `_broken_run.py` (whose faults work here unchanged):

    planner_still   the workers' intents are dropped before they reach
                    the planner: nothing is relocated or replicated, every
                    step still trains (by reads across shards), and only
                    the driver's own liveness check can tell

    routes_stale    after set-up's first steps the device's route mirrors
                    are never rebuilt again: every later step is routed
                    by placement as it stood then (the class of the fault
                    PR 26 found in `DeviceRouter.refresh`). The first
                    probe, the exact checks through the Worker API and
                    the losses all pass; only the probe that runs from
                    the live table after the window can tell

then everything else of a run, as `_broken_run.py` does."""
import os
import sys

if "--rehearse-cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def planner_still():
    from adapm_tpu.core.kv import Worker
    Worker.intent = lambda self, keys, start, end=None: None


def routes_stale():
    from adapm_tpu.ops import fused
    refresh, calls = fused.DeviceRouter.refresh, [0]

    def stale(self):
        calls[0] += 1
        # precompile and the first probe's two steps are the first calls
        if calls[0] <= 8 or self.owner is None:
            refresh(self)
    fused.DeviceRouter.refresh = stale


if __name__ == "__main__":
    {"planner_still": planner_still,
     "routes_stale": routes_stale}[sys.argv[1]]()
    import run
    sys.exit(run.main(sys.argv[2:]))
