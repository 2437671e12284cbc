"""The window layers' decode kernel's share of its roofline **over every
program that holds it**: ``window_wave_attention``'s events in ``jit_decode``
(the lone waves) and in ``jit_prefill`` (the waves that rode in a piece's
program, PR 56), against the ring rows the traced seconds' wave lanes read in
those layers (``wavekernels.attention_share``).  ``window_attn_roofline.itl``
reads ``jit_decode`` alone and falls silent where every wave rides.  Nothing
where the program counts no carried wave."""
import wavekernels


def read(ctx):
    return wavekernels.attention_share(ctx, "window_wave_attention", True)
