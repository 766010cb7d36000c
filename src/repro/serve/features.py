"""Frozen feature extractors for the serving path.

The dSSFN readout is a convex problem over *whatever features it is
given* — the paper trains on raw inputs, but any frozen map phi(x) works
and the centralized-equivalence argument is unchanged (phi is applied
worker-locally before the solve).  At serve time the artifact records
the extractor SPEC (a string, fully deterministic given its seed), so a
request carries raw inputs and the engine reproduces the exact training
featurization in front of the stack.

Spec grammar (``parse_features``)::

    identity              raw inputs straight through (the default; also
                          spelled None)
    rff:D[:seed]          D random Fourier features
                          sqrt(2/D) * cos(W x + b), W ~ N(0, 1),
                          b ~ U[0, 2*pi), seeded
    relu:D[:seed]         D-dim frozen random ReLU projection
                          relu(W x), W ~ N(0, 1/sqrt(P))
    granite-h-micro[:seed]
                          Granite-4.0-H-Micro (``configs/granite_4_0_h_micro``)
                          at its published size, weights drawn from the
                          seed: the final-normed hidden state (2048) at
                          each text's last token (``models/granite.py``)

Extractors are column-wise maps on column-stacked ``(P, J)`` inputs —
each output column depends only on its input column, which is what makes
the serving engine's shape-bucketed padding bit-exact through them.  A
token extractor's column is one text's ids, padded on the right with
``PAD_ID``; the model is causal, so padding leaves the real positions,
and the pooled feature of the last real one, unchanged.

Weights are materialized lazily once the input dimension is known
(:meth:`FeatureExtractor.materialize`) and are pure functions of
``(spec, input_dim)``, so train-side and serve-side materializations are
bit-identical.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

GRANITE = "granite-h-micro"
_KINDS = ("identity", "rff", "relu", GRANITE)
#: The id token columns are padded with; traffic never draws it as a token.
PAD_ID = 0


def text_lengths(ids, pad_id: int = PAD_ID) -> np.ndarray:
    """Each column's length up to and with its last non-pad id (0 for a
    column of pads).  ids: (S, J) on the host."""
    real = np.asarray(ids) != pad_id
    return np.where(real.any(axis=0), real.shape[0] - np.argmax(real[::-1], axis=0), 0)


@dataclass
class FeatureExtractor:
    """A frozen, seeded, column-wise feature map ``(P, J) -> (D, J)``."""

    kind: str            # one of _KINDS
    dim: int = 0         # D; 0 for identity; the hidden size for a backbone
    seed: int = 0
    #: Materialized parameters (None until the input dim is known; the
    #: identity extractor never materializes anything).  A backbone's
    #: are its weight pytree.
    params: tuple[Array, ...] | dict | None = field(default=None, repr=False)
    input_dim: int | None = field(default=None, repr=False)
    #: The backbone's ``ModelConfig`` (token extractors only).
    model: object = field(default=None, repr=False)

    @property
    def takes_tokens(self) -> bool:
        """Columns of token ids, of any length, rather than P-dim vectors."""
        return self.kind == GRANITE

    def describe(self) -> str:
        if self.kind == "identity":
            return "identity"
        if self.takes_tokens:
            return f"{self.kind}:{self.seed}"
        return f"{self.kind}:{self.dim}:{self.seed}"

    def output_dim(self, input_dim: int | None) -> int:
        return input_dim if self.kind == "identity" else self.dim

    def materialize(self, input_dim: int | None) -> "FeatureExtractor":
        """Bind this extractor to an input dimension, drawing its frozen
        weights.  Deterministic in (kind, dim, seed, input_dim); a token
        extractor's weights are drawn on the device from the seed alone."""
        if self.kind == "identity":
            self.input_dim = input_dim
            return self
        if self.takes_tokens:
            if self.params is None:
                from repro.models import granite

                self.params = jax.jit(granite.init_params, static_argnums=1)(
                    jax.random.PRNGKey(self.seed), self.model)
            return self
        if self.input_dim is not None and self.input_dim != input_dim:
            raise ValueError(
                f"extractor {self.describe()} materialized for input_dim="
                f"{self.input_dim}, got {input_dim}"
            )
        if self.params is None:
            key = jax.random.PRNGKey(self.seed)
            kw, kb = jax.random.split(key)
            if self.kind == "rff":
                w = jax.random.normal(kw, (self.dim, input_dim), jnp.float32)
                b = jax.random.uniform(
                    kb, (self.dim, 1), jnp.float32, 0.0, 2.0 * jnp.pi
                )
                self.params = (w, b)
            else:  # relu
                w = jax.random.normal(
                    kw, (self.dim, input_dim), jnp.float32
                ) / jnp.sqrt(jnp.float32(input_dim))
                self.params = (w,)
            self.input_dim = input_dim
        return self

    def admit(self, x, max_length: int) -> np.ndarray:
        """A token request checked at admission: integer ids in the
        vocabulary, each column a text of 1..``max_length`` tokens;
        returned as int32 cut to its longest text.  Raises ValueError."""
        x = np.asarray(x)
        if x.ndim == 1:
            x = x[:, None]
        if x.ndim != 2 or x.shape[1] < 1 or not np.issubdtype(x.dtype, np.integer):
            raise ValueError(
                f"token requests are column-stacked (S, j) integer ids, got "
                f"{x.dtype} of shape {tuple(x.shape)}"
            )
        vocab = self.model.vocab_size
        if x.min() < 0 or x.max() >= vocab:
            raise ValueError(
                f"request holds ids outside the vocabulary 0..{vocab - 1} "
                "(poison rejected at admission)"
            )
        lengths = text_lengths(x)
        if lengths.min() < 1 or lengths.max() > max_length:
            raise ValueError(
                f"texts must hold 1..{max_length} tokens, got "
                f"{lengths.min()}..{lengths.max()}"
            )
        return x[:lengths.max()].astype(np.int32, copy=False)

    def __call__(self, x: Array) -> Array:
        """Apply to column-stacked ``(P, J)`` inputs (trace-safe: pure
        jnp ops over the materialized frozen weights)."""
        if self.kind == "identity":
            return x
        if self.params is None:
            self.materialize(x.shape[0])
        if self.takes_tokens:
            return backbone_features(self.params, x, self.model)
        if self.kind == "rff":
            w, b = self.params
            return jnp.sqrt(2.0 / self.dim) * jnp.cos(w @ x + b)
        (w,) = self.params
        return jax.nn.relu(w @ x)


def stack_tokens(xs: list[np.ndarray]) -> np.ndarray:
    """Column-stack token requests, padding the shorter ones' rows with
    the pad id (texts are right-padded, so this changes no text)."""
    if len(xs) == 1:
        return xs[0]
    rows = max(x.shape[0] for x in xs)
    return np.concatenate(
        [np.pad(x, ((0, rows - x.shape[0]), (0, 0)), constant_values=PAD_ID) for x in xs],
        axis=1)


def backbone_features(params, ids: Array, model) -> Array:
    """A token backbone's pooled features of column-stacked ids:
    (S, J) -> (hidden, J), under the ``features.backbone`` scope."""
    from repro import profiling
    from repro.models import granite

    with profiling.scope(profiling.BACKBONE):
        return granite.pooled_features(params, ids.T, model, PAD_ID).T


def parse_features(spec: str | None) -> FeatureExtractor | None:
    """``identity | rff:D[:seed] | relu:D[:seed] | granite-h-micro[:seed]``
    -> extractor.

    None and ``"identity"`` both mean raw inputs (returned as None so
    callers can treat "no extractor" uniformly).
    """
    if spec is None or spec == "identity":
        return None
    head, _, rest = spec.partition(":")
    if head not in _KINDS:
        raise ValueError(
            f"unknown feature spec {spec!r}; grammar: identity | "
            "rff:D[:seed] | relu:D[:seed] | granite-h-micro[:seed]"
        )
    parts = rest.split(":") if rest else []
    if head == GRANITE:
        from repro.configs import get_config

        if len(parts) > 1:
            raise ValueError(f"feature spec {spec!r} has trailing segments")
        try:
            seed = int(parts[0]) if parts else 0
        except ValueError as e:
            raise ValueError(f"bad feature spec {spec!r}: {e}") from e
        model = get_config("granite-4.0-h-micro")
        return FeatureExtractor(kind=head, dim=model.d_model, seed=seed, model=model)
    if not parts or not parts[0]:
        raise ValueError(f"feature spec {spec!r} is missing its dimension D")
    try:
        dim = int(parts[0])
        seed = int(parts[1]) if len(parts) > 1 else 0
    except ValueError as e:
        raise ValueError(f"bad feature spec {spec!r}: {e}") from e
    if dim < 1:
        raise ValueError(f"feature spec {spec!r}: D must be >= 1")
    if len(parts) > 2:
        raise ValueError(f"feature spec {spec!r} has trailing segments")
    return FeatureExtractor(kind=head, dim=dim, seed=seed)
