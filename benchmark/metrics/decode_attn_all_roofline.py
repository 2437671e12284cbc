"""The whole-context layers' decode kernel's share of its roofline **over
every program that holds it**: ``decode_wave_attention``'s events in
``jit_decode`` (the lone waves) and in ``jit_prefill`` (the waves that rode in
a piece's program, PR 56), against the rows the traced seconds' wave lanes
read in those layers (``wavekernels.attention_share``).
``decode_attn_roofline.itl`` reads ``jit_decode`` alone and falls silent where
every wave rides.  Nothing where the program counts no carried wave."""
import wavekernels


def read(ctx):
    return wavekernels.attention_share(ctx, "decode_wave_attention", False)
