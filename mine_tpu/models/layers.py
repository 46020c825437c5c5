"""Shared Flax building blocks with torch-compatible semantics.

All convs use NHWC (TPU-native) with *explicit* padding so outputs match
torch's symmetric padding exactly (flax 'SAME' pads asymmetrically for even
strides). Initializers reproduce torch defaults so from-scratch training is
distributionally comparable and converted checkpoints drop in unchanged.
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.nn import initializers

Dtype = jnp.dtype

# torch Conv2d default: kaiming_uniform(a=sqrt(5)) == U(+-sqrt(1/fan_in))
torch_conv_kernel_init = initializers.variance_scaling(
    1.0 / 3.0, "fan_in", "uniform")
# torchvision ResNet conv init: kaiming_normal(mode='fan_out')
resnet_kernel_init = initializers.variance_scaling(2.0, "fan_out", "normal")


def torch_bias_init(key, shape, dtype, fan_in: int):
    bound = 1.0 / math.sqrt(fan_in) if fan_in > 0 else 0.0
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def reflect_pad_strips(x, strips: int):
    """torch ReflectionPad2d(1) of an NHWC batch of images that arrive cut
    into `strips` row strips folded into the batch, image-major and
    strip-minor (row r of image n is row r % hs of batch entry n * strips +
    r // hs, hs = h / strips; MPIDecoder folds, and says why).

    W reflects within a strip. Along H a strip's halo rows are its
    neighbours' edge rows, and only an image's first and last strip reflect:
    padding the strips is padding the image, bit for bit, so a VALID 3x3
    conv of the strips is the strips of the conv of the image.
    """
    x = jnp.pad(x, ((0, 0), (0, 0), (1, 1), (0, 0)), mode="reflect")

    def row(r):
        """Row r of every strip, strips apart: [N, k, 1, w + 2, C]. Only
        these single rows are ever viewed with the strips as an axis; the
        whole tensor keeps its four axes and so its layout on the chip."""
        return x[:, r:r + 1].reshape(
            (x.shape[0] // strips, strips, 1) + x.shape[2:])
    hs = x.shape[1]
    top = jnp.concatenate([row(1)[:, :1], row(hs - 1)[:, :-1]], axis=1)
    bottom = jnp.concatenate([row(0)[:, 1:], row(hs - 2)[:, -1:]], axis=1)
    halo = (x.shape[0], 1) + x.shape[2:]
    return jnp.concatenate([top.reshape(halo), x, bottom.reshape(halo)],
                           axis=1)


class _PartsConv(nn.Module):
    """Conv over concat([x, expand(shared), broadcast(tail)], -1) that never
    forms the concat.

    Holds the FULL [k, k, Cx+Cs+E, F] kernel (checkpoint-identical to the
    plain conv over the concatenated input, channel order [x, shared, tail])
    and sums three parts, each with its own slice of that kernel:

      x       [N, h, w, Cx]  per-plane, N = B*S: the only conv at batch N
      shared  [B, h, w, Cs]  one image for all S planes of an example: ONE
                             conv at batch B, broadcast over S
      tail    [N, E]         spatially constant per plane: a constant map
                             stays constant under reflect padding, so its
                             contribution is values @ sum_kl W[k, l, tail]

    The inputs arrive padded (padding acts per channel, so padding the
    parts is padding the concat). Any part may be absent. The shared conv,
    the tail term and the bias are formed and summed in float32 and added
    once to the per-plane conv's result, so y is rounded to `dtype` once
    after the conv. Autodiff transposes the broadcast to a sum over S: the
    shared part's input and weight gradients are convs at batch B too.

    With `strips` = k > 1, x is [N*k, h/k, w, Cx] (reflect_pad_strips' folding)
    and so is the result; shared and tail stay [B, h, w, Cs] and [N, E],
    and the side term is viewed as strips before it is added.
    """
    features: int
    kernel_size: int
    strides: int
    padding: Tuple          # lax-style ((t, b), (l, r)) spatial padding
    use_bias: bool
    kernel_init: Callable
    bias_init: Callable
    dtype: Optional[Dtype]

    @nn.compact
    def __call__(self, x, shared, tail, strips: int = 1):
        k = self.kernel_size
        Cx, Cs, E = (0 if t is None else t.shape[-1]
                     for t in (x, shared, tail))
        kernel = self.param("kernel", self.kernel_init,
                            (k, k, Cx + Cs + E, self.features), jnp.float32)
        bias = self.param("bias", self.bias_init, (self.features,),
                          jnp.float32) if self.use_bias else None
        # planes N = B*S; B is the shared part's batch when there is one
        N = next(t for t in (x, tail, shared) if t is not None).shape[0]
        if x is not None:
            N //= strips
        B = N if shared is None else shared.shape[0]
        dt = self.dtype or jnp.promote_types(
            (shared if x is None else x).dtype, jnp.float32)

        def conv(inp, w):
            return jax.lax.conv_general_dilated(
                inp, w, window_strides=(self.strides, self.strides),
                padding=self.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))

        def f32(t):
            """Operand of a float32 side product: rounded to `dt` first, so
            the products are those of the conv over the concat (on the TPU
            a float32 product at default precision is one `dt`-wide pass
            either way); the accumulation and the result stay float32."""
            return t.astype(dt).astype(jnp.float32)

        # float32 side term: [F] + [B, 1, h, w, F] + [B, S, 1, 1, F], or
        # with strips [F] + [B, 1, k, h/k, w, F] + [B, S, 1, 1, 1, F]
        fold = (strips,) if strips > 1 else ()
        side = jnp.zeros((), jnp.float32) if bias is None else bias
        if shared is not None:
            s = conv(f32(shared), f32(kernel[:, :, Cx:Cx + Cs]))[:, None]
            if fold:
                s = s.reshape((B, 1) + fold + (s.shape[2] // strips,)
                              + s.shape[3:])
            side = side + s
        if tail is not None:
            w_tail = jnp.sum(kernel[:, :, Cx + Cs:], axis=(0, 1))  # [E, F]
            side = side + (f32(tail) @ f32(w_tail)).reshape(
                (B, N // B) + (1,) * len(fold) + (1, 1, self.features))
        if x is None:
            assert not fold, "a conv with no per-plane part has no strips"
            y = side
        else:
            y = conv(x.astype(dt), kernel[:, :, :Cx].astype(dt))
            y = y.reshape((B, N // B) + fold + y.shape[1:]) + side
        lead = 2 + len(fold)
        return y.reshape((-1,) + y.shape[lead:]).astype(dt)


class Conv(nn.Module):
    """NHWC conv with torch-style symmetric padding and init.

    `shared` ([B, h, w, Cs]) and `const_tail` ([N, E]) are optional call
    args: the conv behaves as if the input were
    concat([x, expand(shared), broadcast(const_tail)], -1), with the same
    parameter shapes/paths as that conv, without the expand or the
    broadcast ever existing (see _PartsConv). `x` may then be None (the
    whole input is shared + tail). Only valid with reflect padding (or
    none): zero padding breaks the constant-map identity at borders.

    `strips` > 1: x arrives, and the result leaves, as row strips folded
    into the batch (reflect_pad_strips); reflect padding by 1 only.
    """
    features: int
    kernel_size: int = 3
    strides: int = 1
    padding: Optional[int] = None  # default: (k-1)//2 like torch common usage
    use_bias: bool = True
    pad_mode: str = "zeros"  # "zeros" | "reflect"
    kernel_init: Callable = torch_conv_kernel_init
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x, shared=None, const_tail=None, strips: int = 1):
        k = self.kernel_size
        p = (k - 1) // 2 if self.padding is None else self.padding
        pad = ((p, p), (p, p))
        assert strips == 1 or (p == 1 and self.pad_mode == "reflect"), \
            "strips need the halo of a reflect pad by 1"
        if p > 0 and self.pad_mode == "reflect":
            def reflect(t):
                return t if t is None else jnp.pad(
                    t, ((0, 0), (p, p), (p, p), (0, 0)), mode="reflect")
            x = reflect_pad_strips(x, strips) if strips > 1 else reflect(x)
            shared = reflect(shared)
            pad = ((0, 0), (0, 0))
        fan_in = k * k * sum(t.shape[-1] for t in (x, shared, const_tail)
                             if t is not None)
        bias_init = lambda key, shape, dtype=jnp.float32: torch_bias_init(  # noqa: E731
            key, shape, dtype, fan_in)
        if shared is not None or const_tail is not None:
            assert self.pad_mode == "reflect" or p == 0, \
                "shared / const_tail need reflect (or no) padding"
            return _PartsConv(
                features=self.features, kernel_size=k,
                strides=self.strides, padding=pad,
                use_bias=self.use_bias, kernel_init=self.kernel_init,
                bias_init=bias_init, dtype=self.dtype,
                name="conv")(x, shared, const_tail, strips)
        conv = nn.Conv(
            features=self.features,
            kernel_size=(k, k),
            strides=(self.strides, self.strides),
            padding=pad,
            use_bias=self.use_bias,
            kernel_init=self.kernel_init,
            bias_init=bias_init,
            dtype=self.dtype,
            name="conv",
        )
        return conv(x)


class BatchNorm(nn.Module):
    """torch-compatible BatchNorm2d (momentum 0.1, eps 1e-5), float32 stats.

    Without an axis_name this is still *synchronized* across data-parallel
    shards under GSPMD/jit: the batch axis is a plain array axis of the global
    computation, so the mean/var are global means and XLA inserts the
    cross-replica collectives — the SPMD equivalent of the reference's
    SyncBatchNorm (synthesis_task.py:106-111).
    """
    use_running_average: bool
    momentum: float = 0.1
    epsilon: float = 1e-5
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x):
        out_dtype = x.dtype if self.dtype is None else self.dtype
        norm = nn.BatchNorm(
            use_running_average=self.use_running_average,
            momentum=1.0 - self.momentum,  # flax: ra = m*ra + (1-m)*batch
            epsilon=self.epsilon,
            dtype=jnp.float32,
            name="bn",
        )
        return norm(x.astype(jnp.float32)).astype(out_dtype)


def max_pool_3x3_s2(x):
    """torch MaxPool2d(3, stride=2, padding=1) — pads with -inf, not zeros."""
    return nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))


def upsample_nearest_2x(x):
    """torch UpsamplingNearest2d(scale_factor=2) on NHWC."""
    return jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)


def downsample_nearest(x, factor: int):
    """torch nn.Upsample(size=H/2**s) nearest for exact integer factors is a
    strided slice (index floor(i*factor)). Reference: synthesis_task.py:129-133.
    """
    if factor == 1:
        return x
    return x[:, ::factor, ::factor, :]


class ConvBlock(nn.Module):
    """Reflect-pad 3x3 conv (with bias) + BN + ELU.

    Reference: monodepth2/layers.py:106-120 (ConvBlock = Conv3x3 + BN + ELU,
    Conv3x3 uses ReflectionPad2d). `shared` / `const_tail` / `strips` are
    Conv's: BN and ELU act on the summed conv output at the full batch
    either way (BN reduces over all axes but the last: the same elements
    whether an image is one batch entry or `strips` of them).
    """
    features: int
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x, train: bool, shared=None, const_tail=None,
                 strips: int = 1):
        x = Conv(self.features, 3, pad_mode="reflect", dtype=self.dtype,
                 name="conv3x3")(x, shared=shared, const_tail=const_tail,
                                 strips=strips)
        x = BatchNorm(use_running_average=not train, dtype=self.dtype,
                      name="bn")(x)
        return nn.elu(x)


class ConvBNLeaky(nn.Module):
    """kxk conv (no bias, zero pad) + BN + LeakyReLU(0.1).

    Reference: depth_decoder.conv (depth_decoder.py:17-32, batchnorm branch).
    """
    features: int
    kernel_size: int
    dtype: Optional[Dtype] = None

    @nn.compact
    def __call__(self, x, train: bool):
        x = Conv(self.features, self.kernel_size, use_bias=False,
                 dtype=self.dtype, name="conv")(x)
        x = BatchNorm(use_running_average=not train, dtype=self.dtype,
                      name="bn")(x)
        return nn.leaky_relu(x, negative_slope=0.1)
