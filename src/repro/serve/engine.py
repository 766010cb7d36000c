"""ServeEngine: device-resident dSSFN weights, compile-once batched forward.

The serving hot path is the training-time propagate path run forward —
``y_{l+1} = relu(W_{l+1} y_l)`` over the assembled weights, then the
final readout ``O_L y_L`` — executed as ONE jitted program per
*(shape bucket, input dtype)*:

- **Shape bucketing.**  Request batch sizes are arbitrary; compiling a
  lowering per size would re-trace on every novel request.  The engine
  pads each batch out to the smallest configured bucket that fits (and
  chunks batches larger than the biggest bucket), so the whole request
  distribution hits a small fixed set of lowerings — ``lowerings`` /
  ``cache_info()`` mirror the ``ConsensusBackend`` executable cache and
  the compile-count tests assert exactly one lowering per bucket
  actually used.
- **Bit-exact padding.**  Every op in the forward is column-wise (each
  output column is a function of its input column only), so the padded
  columns cannot perturb the real ones: bucketed, padded, and
  micro-batched execution return bit-identical results for the real
  columns — the serving half of the paper's centralized equivalence,
  asserted by ``tests/test_serve.py``.
- **Weights as operands.**  Device-resident weights ride into the jitted
  program as operands (never baked jit constants — the backend cache's
  rule), so :meth:`reload` hot-swaps a newer same-shape artifact without
  a single recompile.
- **Token requests.**  Under a token extractor (``granite-h-micro``) a
  request column is one text's ids, right-padded with the pad id, and
  the bucket programs are keyed by ``(texts, length)``: each batch of
  texts is planned into groups of similar length, each padded to the
  bucket with the fewest tokens that holds its texts and its longest
  text (:meth:`plan_tokens`).  Ids stay integers into the program.
- **Kernel routing.**  ``use_kernels=True`` routes each propagation
  through the ``matmul_relu`` Pallas kernel on 128-aligned shapes — the
  propagate half of the training engine's fused ``propagate_gram``
  kernel (serving needs no Gram, so the plain fused matmul+relu is the
  right kernel); misaligned shapes fall back to the einsum path, exactly
  like training.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Callable, Hashable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ssfn as ssfn_lib
from repro.serve.export import ServeArtifact, load_artifact
from repro.serve.features import (
    PAD_ID,
    backbone_features,
    parse_features,
    stack_tokens,
    text_lengths,
)

Array = jax.Array

#: Default shape-bucket ladder: powers of two.  Only buckets a request
#: size actually lands in are ever lowered.
DEFAULT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)
#: Default ``(texts, length)`` buckets of a token extractor.
DEFAULT_TOKEN_BUCKETS = ((1, 512), (2, 512), (1, 1024), (2, 1024), (1, 2048), (2, 2048),
                         (1, 4096), (1, 8192))

#: Bound on cached executables (one per (bucket, dtype) in practice —
#: far below this; FIFO eviction keeps pathological dtype churn correct).
_EXEC_CACHE_SIZE = 64


def _tokens(bucket: tuple[int, int]) -> int:
    return bucket[0] * bucket[1]


class ServeEngine:
    """Serve a trained dSSFN stack with compile-once batched inference.

    engine = ServeEngine("artifact_dir", buckets=(1, 8, 32))
    logits = engine.forward(x)          # x: (P_raw, J) column-stacked
    """

    def __init__(
        self,
        artifact: ServeArtifact | str,
        *,
        buckets: tuple[int, ...] | None = None,
        use_kernels: bool = False,
        dtype=jnp.float32,
    ):
        if isinstance(artifact, str):
            artifact = load_artifact(artifact)
        if not isinstance(artifact, ServeArtifact):
            raise TypeError(
                f"expected a ServeArtifact or artifact path, got "
                f"{type(artifact).__name__}"
            )
        self.artifact = artifact
        self.num_classes = artifact.num_classes
        self.dtype = jnp.dtype(dtype)
        self.use_kernels = bool(use_kernels)

        self.extractor = parse_features(artifact.features)
        self.takes_tokens = self.extractor is not None and self.extractor.takes_tokens
        if self.takes_tokens:
            pairs = {(int(t), int(n)) for t, n in (buckets or DEFAULT_TOKEN_BUCKETS)}
            if not pairs or min(min(b) for b in pairs) < 1:
                raise ValueError(f"token buckets must be (texts, length) pairs of "
                                 f"positive ints, got {buckets}")
            #: Fewest tokens first, so the first fit is the cheapest.
            self.buckets = tuple(sorted(pairs, key=lambda b: (_tokens(b), b[1])))
            self.max_batch = max(t for t, _ in self.buckets)
            self.max_length = max(n for _, n in self.buckets)
            #: A batch of texts may take the largest bucket's tokens.
            self.max_tokens = max(_tokens(b) for b in self.buckets)
        else:
            buckets = tuple(sorted(set(buckets or DEFAULT_BUCKETS)))
            if not buckets or buckets[0] < 1:
                raise ValueError(f"buckets must be positive ints, got {buckets}")
            self.buckets = buckets
            self.max_batch = buckets[-1]
        #: Batch dimension requests arrive with (the extractor's input
        #: when one is configured, else the stack's own input dim).
        self.request_dim: int | None = (
            artifact.input_dim if self.extractor is None else None
        )
        self._feat_params: tuple = ()

        self._device_weights = None
        self._load_weights(artifact.params)

        # Executable cache, ConsensusBackend-style: one jitted forward
        # per (bucket, dtype); ``lowerings`` counts actual traces.
        self._exec_cache: OrderedDict[Hashable, Callable] = OrderedDict()
        self.lowerings = 0
        self.cache_hits = 0

    # ------------------------------------------------------------------
    # Weights
    # ------------------------------------------------------------------
    def _load_weights(self, params: ssfn_lib.SSFNParams) -> None:
        q = self.num_classes
        ws = ssfn_lib.assemble_weights(params, q)
        self._device_weights = (
            tuple(jax.device_put(jnp.asarray(w, self.dtype)) for w in ws),
            jax.device_put(jnp.asarray(params.o[-1], self.dtype)),
        )

    def reload(self, artifact: ServeArtifact | str) -> None:
        """Hot-swap a newer artifact.  Weights are program *operands*,
        so a same-shape reload reuses every cached executable (zero
        recompiles); a shape change is rejected — deploy shape changes
        as a new engine."""
        if isinstance(artifact, str):
            artifact = load_artifact(artifact)
        old_w, old_o = self._device_weights
        new_w = ssfn_lib.assemble_weights(artifact.params, artifact.num_classes)
        old_shapes = [tuple(w.shape) for w in old_w] + [tuple(old_o.shape)]
        new_shapes = [tuple(w.shape) for w in new_w] + [
            tuple(artifact.params.o[-1].shape)
        ]
        if old_shapes != new_shapes or artifact.features != self.artifact.features:
            raise ValueError(
                f"reload shape/feature mismatch: engine serves {old_shapes} "
                f"(features={self.artifact.features!r}), artifact has "
                f"{new_shapes} (features={artifact.features!r})"
            )
        self.artifact = artifact
        self._load_weights(artifact.params)

    # ------------------------------------------------------------------
    # Bucketing
    # ------------------------------------------------------------------
    def bucket_for(self, batch: int) -> int:
        """Smallest configured bucket that fits ``batch`` (the largest
        bucket for anything bigger — ``forward`` chunks those)."""
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        for b in self.buckets:
            if batch <= b:
                return b
        return self.max_batch

    # ------------------------------------------------------------------
    # Requests: what a runtime asks of the engine, for either kind
    # ------------------------------------------------------------------
    def admit(self, x) -> np.ndarray:
        """A request checked against what this engine serves, as the
        array the engine takes: column-stacked ``(P, j)`` finite values
        (or ``(P,)``), or under a token extractor ``(S, j)`` ids
        (``FeatureExtractor.admit``).  Raises ValueError."""
        if self.takes_tokens:
            return self.extractor.admit(x, self.max_length)
        x = np.asarray(x)
        if x.ndim == 1:
            x = x[:, None]
        if x.ndim != 2 or x.shape[1] < 1:
            raise ValueError(
                f"requests are column-stacked (P, j) arrays, got shape "
                f"{tuple(x.shape)}"
            )
        expect = self.request_dim
        if expect is not None and x.shape[0] != expect:
            raise ValueError(
                f"request has {x.shape[0]} feature rows, engine serves "
                f"{expect}"
            )
        if not np.isfinite(x).all():
            raise ValueError(
                "request contains non-finite values (poison rejected at "
                "admission)"
            )
        return x

    def stack(self, xs: list[np.ndarray]) -> np.ndarray:
        """Admitted requests side by side, as one ``forward`` input."""
        if self.takes_tokens:
            return stack_tokens(xs)
        return xs[0] if len(xs) == 1 else np.concatenate(xs, axis=1)

    def batch_fits(self, xs: list[np.ndarray], max_batch: int) -> bool:
        """Whether admitted requests make one batch: at most ``max_batch``
        columns; texts, whose plan takes at most ``max_tokens``."""
        if self.takes_tokens:
            return self.batch_tokens(xs)[1] <= self.max_tokens
        return sum(x.shape[1] for x in xs) <= max_batch

    def batch_tokens(self, xs: list[np.ndarray]) -> tuple[int, int]:
        """Real tokens of a batch of requests, and the tokens of the bucket
        programs the plan runs them in ((0, 0) without a token extractor)."""
        if not self.takes_tokens:
            return 0, 0
        lengths = np.concatenate([text_lengths(x) for x in xs])
        plan = self.plan_tokens(lengths)
        return int(lengths.sum()), sum(_tokens(b) for _, b in plan)

    def batch_bucket(self, xs: list[np.ndarray]) -> int:
        """The bucket a batch runs in: its columns' bucket, or the tokens
        of its plan's programs."""
        if self.takes_tokens:
            return self.batch_tokens(xs)[1]
        return self.bucket_for(sum(x.shape[1] for x in xs))

    def token_bucket(self, texts: int, length: int) -> tuple[int, int] | None:
        """The ``(texts, length)`` bucket with the fewest tokens that holds
        ``texts`` texts of at most ``length`` tokens, or None."""
        return next((b for b in self.buckets if texts <= b[0] and length <= b[1]), None)

    def plan_tokens(self, lengths) -> list[tuple[np.ndarray, tuple[int, int]]]:
        """Group texts of ``lengths`` into ``(columns, bucket)`` programs.
        Texts go longest first; a text joins the current group when the
        group's bucket with it costs no more tokens than the group's
        bucket and the text's own bucket apart, else it starts a group."""
        lengths = np.asarray(lengths)
        order = np.argsort(-lengths, kind="stable")
        groups: list[list[int]] = []
        for i in order:
            if groups:
                group = groups[-1]
                longest = int(lengths[group[0]])
                joined = self.token_bucket(len(group) + 1, longest)
                apart = self.token_bucket(len(group), longest)
                alone = self.token_bucket(1, int(lengths[i]))
                if joined is not None and _tokens(joined) <= _tokens(apart) + _tokens(alone):
                    group.append(int(i))
                    continue
            groups.append([int(i)])
        return [(np.asarray(g), self.token_bucket(len(g), int(lengths[g[0]])))
                for g in groups]

    def _chunks(self, j: int) -> list[int]:
        """Split a batch of ``j`` columns into per-executable chunk sizes."""
        out, left = [], j
        while left > self.max_batch:
            out.append(self.max_batch)
            left -= self.max_batch
        out.append(left)
        return out

    # ------------------------------------------------------------------
    # Executable cache
    # ------------------------------------------------------------------
    def _executable(self, bucket, dtype) -> Callable:
        key = (bucket, jnp.dtype(dtype).name)
        jitted = self._exec_cache.get(key)
        if jitted is not None:
            self.cache_hits += 1
            return jitted

        def forward_program(weights, o_last, feat_params, x):
            # Trace-time only: dispatch-cache hits never re-enter here.
            self.lowerings += 1
            return self._forward_program(weights, o_last, feat_params, x)

        jitted = jax.jit(forward_program)
        self._exec_cache[key] = jitted
        while len(self._exec_cache) > _EXEC_CACHE_SIZE:
            self._exec_cache.popitem(last=False)
        return jitted

    def _forward_program(self, weights, o_last, feat_params, x):
        """The bucket program body (traceable, counter-free): features ->
        propagate stack -> readout.  ``_executable`` jits it with a
        lowering counter; ``lowering_texts`` lowers it standalone."""
        if not self.takes_tokens:
            x = x.astype(self.dtype)
        if self.extractor is not None:
            x = self._apply_features(feat_params, x)
        y = x
        for w in weights:
            y = self._propagate(w, y)
        if self.takes_tokens:
            # The pooled features too, for checks of the backbone alone.
            return o_last @ y, x
        return o_last @ y

    def lowering_texts(
        self,
        *,
        bucket: int | None = None,
        dtype=None,
        request_dim: int | None = None,
    ) -> dict[str, str]:
        """Lower (never execute) one bucket program and return its
        ``{"stablehlo": ..., "hlo": ...}`` texts — the
        ``repro.analysis`` probe surface, mirroring
        ``ConsensusBackend.lowering_texts``.  Uses a standalone jit so
        the executable cache and ``lowerings`` counter stay untouched."""
        if bucket is None:
            bucket = self.buckets[0]
        if bucket not in self.buckets:
            raise ValueError(
                f"bucket {bucket} not in configured buckets {self.buckets}"
            )
        dtype = self.dtype if dtype is None else jnp.dtype(dtype)
        if request_dim is None:
            request_dim = (
                self.request_dim
                if self.request_dim is not None
                else self.artifact.input_dim
            )
        self._materialize_features(request_dim)
        weights, o_last = self._device_weights
        if self.takes_tokens:
            x_spec = jax.ShapeDtypeStruct(bucket[::-1], jnp.int32)
        else:
            x_spec = jax.ShapeDtypeStruct((request_dim, int(bucket)), dtype)
        lowered = jax.jit(self._forward_program).lower(
            weights, o_last, self._feat_params, x_spec
        )
        return {
            "stablehlo": lowered.as_text(),
            "hlo": lowered.compile().as_text(),
        }

    def _propagate(self, w: Array, y: Array) -> Array:
        if self.use_kernels:
            from repro.kernels.common import aligned

            if aligned(*w.shape, y.shape[1]):
                from repro.kernels.matmul_relu import matmul_relu

                return matmul_relu(w, y).astype(y.dtype)
        return jax.nn.relu(w @ y)

    def _apply_features(self, feat_params, x):
        ex = self.extractor
        if ex.takes_tokens:
            return backbone_features(feat_params, x, ex.model).astype(self.dtype)
        if ex.kind == "rff":
            w, b = feat_params
            return jnp.sqrt(2.0 / ex.dim) * jnp.cos(w @ x + b)
        (w,) = feat_params
        return jax.nn.relu(w @ x)

    def cache_info(self) -> dict:
        """Executable-cache counters in the schema shared with
        ``ConsensusBackend.cache_info`` (``entries``/``lowerings``/
        ``cache_hits``/``keys`` — ``repro.analysis.retrace`` drives
        both), plus the serve-specific ``buckets`` view."""
        return {
            "entries": len(self._exec_cache),
            "buckets": [k[0] for k in self._exec_cache],
            "lowerings": self.lowerings,
            "cache_hits": self.cache_hits,
            "keys": [repr(k) for k in self._exec_cache],
        }

    def describe(self) -> str:
        return (
            f"ServeEngine({self.artifact.describe()}, buckets="
            f"{list(self.buckets)}, use_kernels={self.use_kernels})"
        )

    # ------------------------------------------------------------------
    # Inference
    # ------------------------------------------------------------------
    def _materialize_features(self, request_dim: int) -> None:
        if self.extractor is None:
            return
        if self._feat_params:
            return
        if self.takes_tokens:
            request_dim = None
        self.extractor.materialize(request_dim)
        if self.extractor.output_dim(request_dim) != self.artifact.input_dim:
            raise ValueError(
                f"feature extractor {self.extractor.describe()} emits "
                f"{self.extractor.output_dim(request_dim)}-dim features, "
                f"stack expects {self.artifact.input_dim}"
            )
        self._feat_params = jax.device_put(self.extractor.params)
        self.request_dim = request_dim

    def _forward_bucket(self, x: Array) -> Array:
        """One padded bucket through the cached executable.
        x: (P, j) with j <= max_batch; returns (Q, j)."""
        j = x.shape[1]
        bucket = self.bucket_for(j)
        if j < bucket:
            pad = jnp.zeros((x.shape[0], bucket - j), x.dtype)
            x = jnp.concatenate([x, pad], axis=1)
        weights, o_last = self._device_weights
        out = self._executable(bucket, x.dtype)(
            weights, o_last, self._feat_params, x
        )
        return out[:, :j] if j < bucket else out

    def forward(self, x) -> Array:
        """Logits ``O_L y_L`` for column-stacked requests ``x``:
        (P, J) -> (Q, J); a single sample may arrive as (P,).  Under a
        token extractor, (S, J) integer ids, each column one text, and
        the logits come back on the host."""
        if self.takes_tokens:
            return self._forward_tokens(x)
        x = jnp.asarray(x)
        if x.ndim == 1:
            x = x[:, None]
        if x.ndim != 2:
            raise ValueError(
                f"requests are column-stacked (P, J) arrays, got shape "
                f"{tuple(x.shape)}"
            )
        self._materialize_features(x.shape[0])
        expect = self.request_dim
        if expect is not None and x.shape[0] != expect:
            raise ValueError(
                f"request has {x.shape[0]} feature rows, engine serves "
                f"{expect} ({self.artifact.describe()})"
            )
        j = x.shape[1]
        if j <= self.max_batch:
            return self._forward_bucket(x)
        outs, start = [], 0
        for size in self._chunks(j):
            outs.append(self._forward_bucket(x[:, start:start + size]))
            start += size
        return jnp.concatenate(outs, axis=1)

    def forward_features(self, ids) -> tuple[np.ndarray, np.ndarray]:
        """Logits and the backbone's pooled features of token columns,
        from the same bucket programs as :meth:`forward`:
        (S, J) -> ((Q, J), (hidden, J)) on the host."""
        if not self.takes_tokens:
            raise ValueError("forward_features needs a token extractor")
        return self._forward_tokens(ids, with_features=True)

    def _forward_tokens(self, ids, *, with_features: bool = False):
        """Plan the texts into bucket groups, run each, and return the
        logits (and features) on the host: host slices compile nothing."""
        ids = np.asarray(ids)
        if ids.ndim == 1:
            ids = ids[:, None]
        if ids.ndim != 2 or not np.issubdtype(ids.dtype, np.integer):
            raise ValueError(
                f"token requests are column-stacked (S, J) integer ids, got "
                f"{ids.dtype} of shape {tuple(ids.shape)}"
            )
        lengths = text_lengths(ids)
        if lengths.min() < 1 or lengths.max() > self.max_length:
            raise ValueError(
                f"texts must hold 1..{self.max_length} tokens, got lengths "
                f"{lengths.min()}..{lengths.max()}"
            )
        self._materialize_features(None)
        weights, o_last = self._device_weights
        logits = np.empty((self.num_classes, ids.shape[1]), np.float32)
        feats = np.empty((self.extractor.dim, ids.shape[1]), np.float32) if with_features else None
        for cols, (texts, length) in self.plan_tokens(lengths):
            block = np.full((length, texts), PAD_ID, np.int32)
            rows = min(length, ids.shape[0])
            block[:rows, :len(cols)] = ids[:rows, cols]
            out, phi = self._executable((texts, length), block.dtype)(
                weights, o_last, self._feat_params, block
            )
            logits[:, cols] = np.asarray(out)[:, :len(cols)]
            if with_features:
                feats[:, cols] = np.asarray(phi)[:, :len(cols)]
        return (logits, feats) if with_features else logits

    __call__ = forward

    def classify(self, x) -> Array:
        """argmax labels for column-stacked requests."""
        return jnp.argmax(self.forward(x), axis=0)
