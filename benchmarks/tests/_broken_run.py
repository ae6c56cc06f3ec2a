"""A run with the timed path broken underneath, for test_broken_path.py:

    step_unchanged   the compiled fused step returns its pools as it got
                     them (the loss is still computed)
    example_dropped  the step's loss leaves the batch's last example out
    lr_off_1pct      the compiled step applies 1.01 x the learning rate it
                     is handed
    answer_altered   a served lookup comes back with one value changed

then everything else of a run: with `--rehearse-cpu` as the tests drive it,
without it on the chip at the cell's own size (PERF.md section 2 has those
readings)."""
import os
import sys

if "--rehearse-cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def step_unchanged():
    from adapm_tpu.ops import fused
    build = fused._build_device_routed_body

    def broken(*a, **kw):
        body = build(*a, **kw)

        def step(pools, locstat, *rest):
            _, locstat, loss = body(pools, locstat, *rest)
            return pools, locstat, loss
        return step
    fused._build_device_routed_body = broken


def example_dropped():
    from adapm_tpu.ops import fused
    build = fused._build_device_routed_body

    def broken(loss_fn, *a, **kw):
        def short(embs, aux):
            B = next(iter(embs.values())).shape[0]
            return loss_fn({r: v[:-1] for r, v in embs.items()},
                           aux) * ((B - 1) / B)
        return build(short, *a, **kw)
    fused._build_device_routed_body = broken


def lr_off_1pct():
    from adapm_tpu.ops import fused
    build = fused._build_device_routed_body

    def broken(*a, **kw):
        body = build(*a, **kw)

        def step(pools, locstat, tables, keys, local_index, alias, rng_key,
                 aux, lr, eps):
            return body(pools, locstat, tables, keys, local_index, alias,
                        rng_key, aux, lr * 1.01, eps)
        return step
    fused._build_device_routed_body = broken


def answer_altered():
    from adapm_tpu.serve import ServeSession
    lookup = ServeSession.lookup

    def broken(self, keys, deadline_ms=None, out=None):
        rows = lookup(self, keys, deadline_ms, out).copy()
        rows.reshape(-1)[0] += 1.0
        return rows
    ServeSession.lookup = broken


if __name__ == "__main__":
    {"step_unchanged": step_unchanged, "example_dropped": example_dropped,
     "lr_off_1pct": lr_off_1pct,
     "answer_altered": answer_altered}[sys.argv[1]]()
    import run
    sys.exit(run.main(sys.argv[2:]))
