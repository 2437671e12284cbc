"""A served decoder's recurrent-state layer, in a wave and in a prefill piece:
one copy.

``models/kimi_linear.py`` (KDA: a gated delta rule, ops/kda.py) and
``models/nemotron_h.py`` (Mamba-2: a scalar decay, ops/ssd.py) both keep, for a
``"state"`` layer of models/decoder.py's contract, two leaves a slot
(``state_leaves = ("s", "conv")``): the recurrence's state, float32, and the
last ``taps - 1`` inputs of a causal depthwise convolution ``[layers of the
kind, R, (taps - 1) * width]``, as the projection leaves them, in the model's
dtype.  What a layer does with them is one frame around different
projections:

- **a wave** (``_advance``: the projection, then ``_step_slots``): each
  lane's new input joins its slot's tail (``ext [B, taps, width]``), all but
  the oldest go back, and the slot's state moves one position in place: the
  wave kernel, or its oracle where the arena is not the kernels'
  (``_use_kernel()``, the one place that chooses);
- **a piece** of ``L`` lanes (``_piece_state_layer``): the projection over
  every lane's positions at once, then a lane at a time the chunked form from
  the slot's state and tail (zeros for a prompt's first piece), the state and
  the tail of the last valid positions written back;
- **a wave that rides in the piece's program** (models/decoder.py
  ``piece_wave``; ``_piece_state_layer(..., wave)``): the wave's ``B`` rows
  stand behind the piece's through the one projection (its matrices are read
  once a program) and then take ``_step_slots``, the wave's own step and the
  one copy of it, on their own slots behind the last piece lane's writes.  The
  slots of the two are disjoint (a stream prefills or decodes), and the
  state's leaf goes from a lane's ``dynamic_update_slice`` into the kernel's
  aliased operand with no branch between: it is updated in place all the
  way (tests/test_tpu_compile.py holds the compiled programs to it).  A
  backend whose other kinds carry too declares ``piece_wave``
  (``models/nemotron_h.py``); ``models/kimi_linear.py`` does not, its
  attention being a latent cache, and with no wave the piece traces to the
  program it was.

A model sets ``taps, piece, chunk, state_shape`` (a slot's state as the
recurrence walks it) and supplies ``_state_ops()`` -> (wave kernel, its
oracle, the chunked form, the recurrence position by position);
``_state_project(lp, x, dtype)`` -> (the convolution's new inputs ``[n,
width]`` in ``dtype``, what ``_state_inputs`` reads beside them, what only
``_through_state`` reads: any pytrees of a row a position);
``_state_inputs(lp, beside, ext)`` -> the tensors a position that come of
``ext [..., n + taps - 1, width]`` (a wave's lanes stand there as sequences of
one position); ``_through_state(lp, ins, aside, run, pad)`` -> the layer's
output, where ``run(*the recurrence's operands)`` advances the state and
returns its read-out and ``pad(t)`` zeroes a tensor at padded positions (the
model says which inputs a padded position must not move the state by).
"""

from __future__ import annotations


class StateLayer:
    """The shared parts above."""

    def _advance(self, lp, x, s_a, conv_a, rows, lens, ki):
        del lens
        return self._step_slots(
            lp, self._state_project(lp, x["h"], conv_a.dtype), s_a, conv_a,
            rows, ki)

    def _step_slots(self, lp, projected, s_a, conv_a, rows, ki):
        """A wave's step behind its projection (``projected``: what
        ``_state_project`` made of the lanes' rows): -> (s_a, conv_a, o ``[B,
        *]``).  The one copy of the step: a wave of its own runs it
        (``_advance``), and so does the wave that rides in a piece's program
        (``_piece_state_layer``)."""
        import jax
        import jax.numpy as jnp

        from client_tpu.engine.backend_init import pallas_interpret
        from client_tpu.models.decoder import put_slot_tails, slot_tails

        new, beside, aside = projected
        lanes, width = new.shape
        pick, slots, tail = slot_tails(conv_a, ki, rows)
        ext = jnp.concatenate(
            [tail.reshape(lanes, self.taps - 1, width), new[:, None]], axis=1)
        beside = jax.tree_util.tree_map(lambda t: t[:, None], beside)
        ins = [t[:, 0] for t in self._state_inputs(lp, beside, ext)]
        conv_a = put_slot_tails(conv_a, ki, pick, slots, ext)
        kernel, oracle, _, _ = self._state_ops()

        def run(*operands):
            nonlocal s_a
            if self._use_kernel():
                s_a, o = kernel(s_a, *operands, rows, layer=ki,
                                interpret=pallas_interpret())
            else:
                s_a, o = oracle(s_a, *operands, rows, layer=ki)
            return o

        o = self._through_state(lp, ins, aside, run, lambda t: t)
        return s_a, conv_a, o

    def _full_state_layer(self, lp, x, pos):
        """A whole prompt from a zero state and a zero tail, position by
        position (models/experts.py ``make_apply_params``)."""
        import jax.numpy as jnp

        del pos
        new, beside, aside = self._state_project(lp, x, jnp.dtype(self.dtype))
        ext = jnp.concatenate(
            [jnp.zeros((self.taps - 1, new.shape[1]), new.dtype), new])
        *_, recurrence = self._state_ops()
        zero = jnp.zeros(self.state_shape, jnp.float32)
        return self._through_state(
            lp, self._state_inputs(lp, beside, ext), aside,
            lambda *operands: recurrence(*operands, zero)[0], lambda t: t)

    def _piece_state_layer(self, lp, s_a, conv_a, ki, rows, starts, lens, x,
                           pos, wave=None):
        """-> (s_a, conv_a, o ``[L * piece, *]``), lane after lane
        (models/decoder.py ``piece_hidden_fn``).

        **With a wave** (models/decoder.py ``piece_wave``; ``wave``: the
        wave's rows ``[B]`` and lengths): x holds the wave's ``B`` rows behind
        the piece's, the projection runs once over all of them (its matrices
        are read once a program), and behind the last lane the wave's rows go
        through the wave's own step on their slots (``_step_slots``); o is
        then ``[L * piece + B, *]``."""
        import jax
        import jax.numpy as jnp

        del pos
        n, width = self.piece, conv_a.shape[-1] // (self.taps - 1)
        new, beside, aside = self._state_project(lp, x, conv_a.dtype)
        (_, _, chunked, _), outs = self._state_ops(), []
        for i in range(rows.shape[0]):
            row, fresh = rows[i], starts[i] == 0
            valid = jnp.arange(n) < lens[i]

            def own(t):
                return t[i * n:(i + 1) * n]

            def pad(t):
                return jnp.where(
                    jnp.expand_dims(valid, tuple(range(1, t.ndim))), t, 0.0)

            def run(*operands):
                nonlocal s_a
                o, s = chunked(*operands,
                               jnp.where(fresh, 0.0, s_a[ki, row]),
                               chunk=self.chunk)
                s_a = jax.lax.dynamic_update_slice(
                    s_a, s.astype(s_a.dtype)[None, None],
                    (ki, row, 0, 0, 0))
                return o

            tail = jnp.where(fresh, 0, conv_a[ki, row]).reshape(-1, width)
            ext = jnp.concatenate([tail, own(new)])
            ins = self._state_inputs(
                lp, jax.tree_util.tree_map(own, beside), ext)
            outs.append(self._through_state(
                lp, ins, jax.tree_util.tree_map(own, aside), run, pad))
            # The inputs of the last valid positions (with the old tail's,
            # where the piece holds fewer than a tail).
            tail = jax.lax.dynamic_slice(ext, (lens[i], 0),
                                         (self.taps - 1, width))
            conv_a = jax.lax.dynamic_update_slice(
                conv_a, tail.reshape(1, 1, -1), (ki, row, 0))
        if wave is not None:
            w_rows, _ = wave
            s_a, conv_a, o = self._step_slots(
                lp, jax.tree_util.tree_map(
                    lambda t: t[rows.shape[0] * n:], (new, beside, aside)),
                s_a, conv_a, w_rows, ki)
            outs.append(o)
        return s_a, conv_a, jnp.concatenate(outs)
