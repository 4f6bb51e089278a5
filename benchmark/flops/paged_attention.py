"""What one decode step of paged attention needs, whatever the model
family: per token of live context the kernel reads that token's K and V
in every layer (2 x width values) and spends 4 operations per value
pair (q.k and p.v, a multiply and an add each); per row it reads q and
writes o."""

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def decode_step(context_tokens, rows, layers, width, itemsize):
    """(operations, bytes) of the paged attention kernels of one decode
    step over ``context_tokens`` live tokens in ``rows`` streams."""
    ops = 4.0 * context_tokens * width * layers
    nbytes = (2.0 * context_tokens + 2.0 * rows) * width * itemsize \
        * layers
    return ops, nbytes
