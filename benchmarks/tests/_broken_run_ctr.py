"""A run of the DLRM cell with its timed path broken underneath, for
test_ctr_cell.py:

    example_dropped  the step's loss leaves the batch's last example out:
                     its members' rows, its dense features and its label
                     (`_broken_run.py`'s fault of that name drops the last
                     row of every role: here a member of every bag and a
                     row of the dense network, a shape error)

then everything else of a run, as `_broken_run.py`."""
import os
import sys

if "--rehearse-cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def example_dropped():
    from adapm_tpu.ops import fused
    build = fused._build_device_routed_body

    def broken(loss_fn, *a, **kw):
        def short(embs, aux):
            x, y = aux
            B = y.shape[0]
            return loss_fn({"feat": embs["feat"][:, :-1],
                            "dense": embs["dense"]},
                           (x[:-1], y[:-1])) * ((B - 1) / B)
        return build(short, *a, **kw)
    fused._build_device_routed_body = broken


if __name__ == "__main__":
    {"example_dropped": example_dropped}[sys.argv[1]]()
    import run
    sys.exit(run.main(sys.argv[2:]))
