"""Kernels: the paged decode-attention kernel's roofline share in the
traced window (%): the least time for the decode calls' attention (each
active slot over its cached context) over the kernel's device time."""
from bench.layers import roofline

# the only Pallas call of the decode program
MODULE, TARGET = r"^_decode_and_sample$", "tpu_custom_call"


def read(run):
    fam, pub = run.cell.family, run.cell.config["published"]
    block = run.engine["kv_block_size"]

    def work(tick):
        if not tick.decode_ctx:
            return 0.0, 0.0
        return fam.paged_decode_work(pub, tick.decode_ctx, block)

    return roofline(run, MODULE, TARGET, work)
