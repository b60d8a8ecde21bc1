"""Model FLOPs of every prompt and generated token the engine processed
in the traced window (the configuration module's ``prefill_flops`` and
``token_flops``; for a dense decoder ``workcount.decoder_*``: 2 per
matmul parameter plus causal attention, the head where logits are
needed) over the window's wall time at the chip's peak."""


def read(record):
    c = record["counters"]
    if not c.get("traced_steps") or not record.get("traced_s"):
        return None
    return 100.0 * c["traced_flops"] / (record["traced_s"]
                                        * record["peak"]["flops"])
