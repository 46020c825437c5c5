"""Full MPI predictor: encoder + disparity-conditioned decoder.

Replaces SynthesisTask.mpi_predictor (synthesis_task.py:222-228) as a single
Flax module so the whole forward lives in one XLA graph.

Plane-chunked decoding (`plane_chunks > 1`): the decoder's effective batch is
B*S (depth_decoder.py:105-116) and its activations are the step's HBM peak —
B=8 at LLFF shapes overflows a 16 GB v5e (round-2 notes in git history).
Chunking runs
the decoder plane_chunks times on S/plane_chunks planes each, with each call
under jax.checkpoint, so the backward pass holds ONE chunk's activations at
a time instead of all B*S.

BN-statistics decision (made explicit, was deferred in ROADMAP): the decoder
ConvBlocks BatchNorm over the B*S batch; chunked training normalizes each
chunk by its OWN batch statistics ("ghost batch norm" over B*S/plane_chunks
examples) and the running averages see every chunk sequentially. The
receptive-field neck (whose batch is B, not B*S — plane-independent) is
computed ONCE per step outside the chunk loop, so its statistics and FLOPs
are identical to the unchunked model. Eval-mode outputs (running stats, no
dropout) are bitwise-independent of chunking, so converted reference
checkpoints behave identically; only training dynamics differ, in the
well-understood ghost-BN direction. GroupNorm was rejected: it would break
released-checkpoint compatibility.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from mine_tpu.models.decoder import MPIDecoder
from mine_tpu.models.resnet import ResnetEncoder, num_ch_enc


class MPIPredictor(nn.Module):
    num_layers: int = 50
    pos_encoding_multires: int = 10
    use_alpha: bool = False
    scales: Sequence[int] = (0, 1, 2, 3)
    sigma_dropout_rate: float = 0.0
    dtype: Optional[jnp.dtype] = None
    mesh: Optional[Any] = None  # forwarded to the decoder's B*S sharding
    plane_chunks: int = 1  # decoder calls over the S axis (memory knob)
    decoder_variant: str = "reference"  # "packed": stride-2 output stage
    # with 4x channels + depth-to-space head (models/decoder.py variant doc)

    def setup(self):
        if self.decoder_variant not in ("reference", "packed"):
            # fail at construction: a typo ("packed_head", "Packed") would
            # otherwise silently build the reference geometry and train the
            # wrong architecture under the right name
            raise ValueError(
                f"model.decoder_variant must be 'reference' or 'packed', "
                f"got {self.decoder_variant!r}")
        self.backbone = ResnetEncoder(num_layers=self.num_layers,
                                      dtype=self.dtype, name="backbone")
        decoder_cls = MPIDecoder
        if self.plane_chunks > 1:
            # per-chunk remat is the point of chunking: backward recomputes
            # one chunk's decoder forward at a time (train and neck_only
            # args are static)
            decoder_cls = nn.remat(MPIDecoder, static_argnums=(3, 4))
        self.decoder = decoder_cls(
            num_ch_enc=num_ch_enc(self.num_layers),
            pos_encoding_multires=self.pos_encoding_multires,
            use_alpha=self.use_alpha,
            scales=tuple(self.scales),
            sigma_dropout_rate=self.sigma_dropout_rate,
            variant=self.decoder_variant,
            dtype=self.dtype,
            mesh=self.mesh,
            name="decoder")

    def __call__(self, src_imgs, disparity, train: bool):
        """src_imgs [B,H,W,3] in [0,1]; disparity [B,S] ->
        list of 4 volumes [B,S,4,H/2^s,W/2^s] (scale order 0,1,2,3)."""
        return self.decode(self.encode(src_imgs, train), disparity, train)

    def encode(self, src_imgs, train: bool):
        """Backbone half, exposed as a stage boundary: src_imgs [B,H,W,3]
        -> tuple of 5 feature maps (strides 2..32). Applied standalone via
        `method="encode"` with only the backbone param/stat subtrees
        (mine_tpu/parallel/pipeline.py); __call__ composes encode+decode so
        the fused trace is unchanged."""
        # named_scope -> HLO metadata: profiler traces attribute time to
        # encoder vs decoder without guesswork
        with jax.named_scope("encoder"):
            return self.backbone(src_imgs, train)

    def decode(self, feats, disparity, train: bool):
        """Decoder half (plane-chunk logic included): encoder feature tuple
        + disparity [B,S] -> the 4-scale MPI list. Stage-boundary
        counterpart of `encode` (applied via `method="decode"`)."""
        S = disparity.shape[1]
        chunks = self.plane_chunks
        if chunks > 1 and S % chunks != 0:
            # e.g. the coarse-to-fine refinement pass with a different S; a
            # single unchunked call stays correct but holds the full B*S
            # activations — warn loudly, since at B=8 LLFF shapes that is
            # the HBM overflow this knob exists to prevent (the trainer
            # rejects non-divisible num_bins_coarse statically; this path
            # is for secondary passes with their own S)
            _warn_unchunked(S, chunks)
            chunks = 1
        with jax.named_scope("decoder"):
            if chunks == 1:
                # the remat-wrapped decoder's static_argnums cover the
                # neck args, so pass them explicitly on every path
                outputs = self.decoder(list(feats), disparity, train,
                                       False, None)
            else:
                cs = S // chunks
                neck = self.decoder(list(feats), disparity, train, True, None)
                outs = [self.decoder(list(feats),
                                     disparity[:, c * cs:(c + 1) * cs],
                                     train, False, neck)
                        for c in range(chunks)]
                outputs = {s: jnp.concatenate([o[s] for o in outs], axis=1)
                           for s in outs[0]}
        return [outputs[s] for s in sorted(outputs)]


_warned_unchunked = set()


def _warn_unchunked(S: int, chunks: int) -> None:
    """One-time trace-time notice when plane chunking is bypassed."""
    if (S, chunks) in _warned_unchunked:
        return
    _warned_unchunked.add((S, chunks))
    import warnings
    warnings.warn(
        f"plane_chunks={chunks} does not divide S={S}; decoder runs "
        f"UNCHUNKED for this pass (full B*S activation footprint)")
