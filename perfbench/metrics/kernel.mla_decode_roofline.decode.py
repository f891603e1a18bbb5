"""kernel.mla_decode_roofline.decode: mla_paged_attention calls inside jit_serve_decode_step: the latent rows the decoding rows see, read once / 819 GB/s (or their FLOPs / peak, whichever bounds) / their device time."""
from perfbench.harness import readers


def read(run, ctx):
    return readers.kernel_roofline(run, ctx, ("mla_paged_attention",),
                                   program=readers.DECODE_PROGRAM)
