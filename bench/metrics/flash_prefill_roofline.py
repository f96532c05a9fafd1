"""Kernels: the flash prefill kernel's roofline share in the traced window
(%): the least time for the bucketed prefill calls' causal attention, at
their padded (rows, bucket) shapes, over the kernel's device time."""
from bench.layers import roofline

# the only Pallas call of the bucketed prefill program
MODULE, TARGET = r"^_prefill_and_splice$", "tpu_custom_call"


def read(run):
    fam, pub = run.cell.family, run.cell.config["published"]
    rows_of = run.engine["batch_rows"]

    def work(tick):
        f = b = 0.0
        for rows, bucket, _lens in tick.prefills:
            df, db = fam.flash_prefill_work(pub, rows_of(rows), bucket)
            f += df
            b += db
        return f, b

    return roofline(run, MODULE, TARGET, work)
