"""A run of the tiered serving cell with the tier plane broken
underneath, for test_bags_tier_cell.py (and, without `--rehearse-cpu`,
on the chip at the cell's own size):

    dirty_dropped   a demotion drops EVERY victim's device row without a
                    readback, written or not: a row pushed to while hot
                    loses the push when it is demoted (the cold copy is
                    the value from before it)
    cold_stale      the staged operand of a bag read carries zeros for
                    every cold member (the cold store is not read)

then everything else of a run, as `_broken_run.py`."""
import os
import sys

if "--rehearse-cpu" in sys.argv:
    os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))


def dirty_dropped():
    import numpy as np
    from adapm_tpu.tier import promote
    demote = promote.demote_rows

    def broken(store, shard, slots, **kw):
        # forge the promotion's record: every victim reads as unwritten
        res = store.res
        slots = np.unique(np.asarray(slots, dtype=np.int64))
        rows = res.dev_row[shard, slots]
        hot = rows >= 0
        res.promo_epoch[shard, rows[hot]] = \
            store.main_epoch[shard, slots[hot]]
        return demote(store, shard, slots, **kw)
    promote.demote_rows = broken


def cold_stale():
    import numpy as np
    from adapm_tpu.tier import quant
    read = quant.QuantCold.read
    state = {"on": False}

    def broken(self, sh, sl):
        rows = read(self, sh, sl)
        return np.zeros_like(rows) if state["on"] else rows
    quant.QuantCold.read = broken
    # only under the serve plane's bag reads: promotions and read_main
    # keep the true rows, so the replies alone see it
    from adapm_tpu.tier import coldpath
    gather = coldpath.gather_pool_tiered

    def staged(*a, **kw):
        state["on"] = True
        try:
            return gather(*a, **kw)
        finally:
            state["on"] = False
    coldpath.gather_pool_tiered = staged


if __name__ == "__main__":
    {"dirty_dropped": dirty_dropped, "cold_stale": cold_stale}[sys.argv[1]]()
    import run
    sys.exit(run.main(sys.argv[2:]))
