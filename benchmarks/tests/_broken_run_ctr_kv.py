"""A run of the four-shard DLRM cell with a fault planted under its timed
path, beside `_broken_run.py` (`step_unchanged`, `lr_off_1pct`) and
`_broken_run_ctr.py` (`example_dropped`), whose faults work here
unchanged:

    class_deltas_dropped  every sync of the class with the longest rows
                          (the dense network's) first zeroes that class's
                          delta pool: what its replicas' holders wrote
                          since the last sync never reaches main. The
                          feature class syncs as it should

then everything else of a run, as `_broken_run.py` does."""
import os
import sys

if "--rehearse-cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def class_deltas_dropped():
    from adapm_tpu.core.store import ShardedStore
    init, sync = ShardedStore.__init__, ShardedStore.sync_replicas
    longest = [0]

    def noting(self, num_keys, value_length, *a, **kw):
        longest[0] = max(longest[0], value_length)
        init(self, num_keys, value_length, *a, **kw)

    def dropping(self, *a, **kw):
        if self.value_length == longest[0]:
            self.delta = self.delta * 0
        return sync(self, *a, **kw)
    ShardedStore.__init__, ShardedStore.sync_replicas = noting, dropping


if __name__ == "__main__":
    {"class_deltas_dropped": class_deltas_dropped}[sys.argv[1]]()
    import run
    sys.exit(run.main(sys.argv[2:]))
