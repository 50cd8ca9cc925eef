"""Exchange plane: bytes one client puts on the wire per round, the
encoded fusion payload (the codec's own exact count) plus its int32
labels."""


def read(ctx):
    from repro.core.codec import get_codec

    job = ctx["job"]
    shape = (job["batch"], job["seq"], ctx["conf"]["d_fusion"])
    return float(get_codec(job["codec"]).encoded_nbytes(shape)
                 + job["batch"] * job["seq"] * 4)
