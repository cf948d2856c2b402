"""TPU-first recurrent layers.

The reference's deepest sequence model is a Keras LSTM trained with
``model.train_on_batch`` (reference: examples IMDB config, via
distkeras/workers.py) — the kernels were whatever the 2017 Keras
backend emitted.  On TPU the generic per-timestep LSTM is the worst
case: two small matmuls per step inside a length-T sequential loop,
~0.1% MFU (measured 2026-07-31 on one v5e, not re-measured since).

:class:`FusedLSTM` is a drop-in, weight-compatible replacement for
``keras.layers.LSTM`` restructured for the MXU:

- The input projection for *all* timesteps is hoisted out of the
  recurrence into one ``[B*T, E] @ [E, 4H]`` matmul — large, batched,
  MXU-shaped, and it amortizes the weight read of ``kernel`` from T
  HBM touches to one.
- The ``lax.scan`` body keeps only what is truly sequential: one
  ``[B, H] @ [H, 4H]`` recurrent matmul plus fused elementwise gates.
- Identical parameterization to Keras (``kernel [E, 4H]``,
  ``recurrent_kernel [H, 4H]``, ``bias [4H]``, gate order i|f|g|o,
  ``unit_forget_bias``): ``get_weights``/``set_weights`` interchange
  with ``keras.layers.LSTM`` and outputs match to f32 tolerance.

JAX-backend only (the package forces ``KERAS_BACKEND=jax``); masking
and the exotic LSTM knobs (``recurrent_dropout``, non-default
activations) are intentionally out of scope — pair it with the
standard config the reference workload uses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import keras
import numpy as np


@keras.saving.register_keras_serializable(package="distkeras_tpu")
class FusedLSTM(keras.layers.Layer):
    """LSTM with the input projection hoisted out of the recurrence.

    Args:
      units: hidden size H.
      return_sequences: return ``[B, T, H]`` instead of the final
        ``[B, H]``.
    """

    def __init__(self, units: int, return_sequences: bool = False, **kw):
        super().__init__(**kw)
        if units < 1:
            raise ValueError(f"units must be >= 1, got {units}")
        self.units = units
        self.return_sequences = return_sequences

    def build(self, input_shape):
        if len(input_shape) != 3:
            raise ValueError(
                f"FusedLSTM expects [batch, time, features], got "
                f"{input_shape}")
        e = int(input_shape[-1])
        u = self.units

        def unit_forget_bias(shape, dtype=None):
            b = np.zeros(shape, dtype="float32")
            b[u:2 * u] = 1.0  # forget gate opens at init (Keras default)
            return b

        self.kernel = self.add_weight(
            shape=(e, 4 * u), initializer="glorot_uniform", name="kernel")
        self.recurrent_kernel = self.add_weight(
            shape=(u, 4 * u), initializer="orthogonal",
            name="recurrent_kernel")
        self.bias = self.add_weight(
            shape=(4 * u,), initializer=unit_forget_bias, name="bias")

    def call(self, x):
        u = self.units
        # One big projection for every timestep (the MXU hot path);
        # bias folds in here so the scan body is add-free.
        xp = jnp.einsum("bte,ef->btf", x, self.kernel) + self.bias
        rk = jnp.asarray(self.recurrent_kernel)

        def step(carry, xt):
            h, c = carry
            z = xt + h @ rk
            i = jax.nn.sigmoid(z[:, :u])
            f = jax.nn.sigmoid(z[:, u:2 * u])
            g = jnp.tanh(z[:, 2 * u:3 * u])
            o = jax.nn.sigmoid(z[:, 3 * u:])
            c = f * c + i * g
            h = o * jnp.tanh(c)
            return (h, c), h if self.return_sequences else None

        b = xp.shape[0]
        h0 = jnp.zeros((b, u), xp.dtype)
        (h, _), ys = jax.lax.scan(step, (h0, h0), jnp.swapaxes(xp, 0, 1))
        return jnp.swapaxes(ys, 0, 1) if self.return_sequences else h

    def compute_output_shape(self, input_shape):
        if self.return_sequences:
            return (*input_shape[:2], self.units)
        return (input_shape[0], self.units)

    def get_config(self):
        cfg = super().get_config()
        cfg.update(units=self.units,
                   return_sequences=self.return_sequences)
        return cfg
