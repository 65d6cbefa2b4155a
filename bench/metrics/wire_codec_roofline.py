"""The fused wire codec's share of its roofline: the least time its
encodes and decodes need, from the bytes each moves (computed from the
hop's shape [micro-batch rows x seq, d_model]) at the chip's HBM
bandwidth, over their device time in the trace.  The codec does a few
operations per byte, so HBM bandwidth is the bound."""
import re

from harness import flops, trace

#: bytes of one hop activation element entering the encoder and leaving
#: the decoder (the pipeline's compute dtype, bfloat16)
ACT_ITEMSIZE = 2
BLOCK = 256


#: a block-quantized payload and its float32 scales, one per block
CODES = r"\((s8|f8e4m3fn|f8e5m2)\[[\d,]+\], f32\[[\d,]+,1\]\)"
ENCODE = re.compile(r"custom-call:tpu_custom_call " + CODES)
DECODE = re.compile(r"custom-call:tpu_custom_call \S+ <- " + CODES)


def is_encode(name: str) -> bool:
    return bool(ENCODE.search(name))


def is_decode(name: str) -> bool:
    return bool(DECODE.search(name))


def read(obs):
    pipe = obs.cell.traffic.get("pipeline")
    if obs.trace is None or not pipe or pipe["wire_dtype"] == "none":
        return None
    tr, d = obs.cell.traffic, obs.cell.config["hidden_size"]
    rows = tr["batch"] // tr["microbatches"] * tr["seq"]
    per_call = flops.codec_bytes(rows, d, BLOCK, ACT_ITEMSIZE)
    calls = (trace.op_count(obs.trace, is_encode)
             + trace.op_count(obs.trace, is_decode))
    busy = (trace.op_seconds(obs.trace, is_encode)
            + trace.op_seconds(obs.trace, is_decode))
    if not calls or busy <= 0:
        return None
    return 100.0 * calls * per_call / obs.peaks["hbm_bytes_per_s"] / busy
