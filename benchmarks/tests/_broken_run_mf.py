"""A run of the MF cell with its timed path broken underneath, for
test_mf_cell.py:

    example_dropped  the step's loss leaves the batch's last example out,
                     its observed value with it (`_broken_run.py`'s fault
                     of that name drops the rows alone: right for a loss
                     that is handed nothing besides its rows, a shape
                     error for MF's, which is handed the observed values)

    score_short      the pass-end loss walk's score program leaves the
                     batch's last cell out: no step is touched

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
            B = aux.shape[0]
            return loss_fn({r: v[:-1] for r, v in embs.items()},
                           aux[:-1]) * ((B - 1) / B)
        return build(short, *a, **kw)
    fused._build_device_routed_body = broken


def score_short():
    from adapm_tpu.apps import matrix_factorization as mf
    score = mf.mf_sq_error
    mf.mf_sq_error = lambda embs, aux: score(embs, (aux[0], aux[1] - 1))


if __name__ == "__main__":
    {"example_dropped": example_dropped,
     "score_short": score_short}[sys.argv[1]]()
    import run
    sys.exit(run.main(sys.argv[2:]))
