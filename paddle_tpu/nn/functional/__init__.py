"""Functional ops (parity: paddle.nn.functional).

Thin, jit-friendly wrappers over jax.numpy/lax, organized by domain in
the reference's own module layout (python/paddle/nn/functional/
{activation,common,conv,pooling,loss,norm,vision,input,
flash_attention}.py). Where the reference routes through hand-written
CUDA kernels (paddle/phi/kernels/gpu/, paddle/phi/kernels/fusion/), XLA
fusion covers the same ground on TPU; the genuinely hot fused paths
(flash attention, rope/rmsnorm at long seq, paged decode) live in
paddle_tpu.kernels as Pallas implementations and are dispatched from
here when available.
"""

from .activation import (  # noqa: F401
    celu,
    elu,
    gelu,
    glu,
    hardshrink,
    hardsigmoid,
    hardswish,
    hardtanh,
    leaky_relu,
    log_sigmoid,
    log_softmax,
    maxout,
    mish,
    prelu,
    relu,
    relu6,
    rrelu,
    selu,
    sigmoid,
    silu,
    softmax,
    softplus,
    softshrink,
    softsign,
    swiglu,
    swish,
    tanh,
    tanhshrink,
    thresholded_relu,
)
from .common import (  # noqa: F401
    _f32up,
    _v,
    alpha_dropout,
    bilinear,
    channel_shuffle,
    cosine_similarity,
    dropout,
    dropout2d,
    dropout3d,
    fold,
    gumbel_softmax,
    label_smooth,
    pairwise_distance,
    sequence_mask,
    temporal_shift,
    interpolate,
    linear,
    pad,
    unfold,
    upsample,
    zeropad2d,
)
from .conv import (  # noqa: F401
    conv1d,
    conv1d_transpose,
    conv2d,
    conv2d_transpose,
    conv3d,
    conv3d_transpose,
)
from .flash_attention import (  # noqa: F401
    flash_attention,
    scaled_dot_product_attention,
)
from .input import embedding, one_hot  # noqa: F401
from .loss import (  # noqa: F401
    binary_cross_entropy,
    binary_cross_entropy_with_logits,
    cosine_embedding_loss,
    cross_entropy,
    ctc_loss,
    rnnt_loss,
    dice_loss,
    gaussian_nll_loss,
    hinge_embedding_loss,
    kl_div,
    l1_loss,
    log_loss,
    margin_ranking_loss,
    mse_loss,
    multi_label_soft_margin_loss,
    nll_loss,
    poisson_nll_loss,
    shift_labels,
    sigmoid_focal_loss,
    smooth_l1_loss,
    soft_margin_loss,
    softmax_cross_entropy_rows,
    square_error_cost,
    triplet_margin_loss,
)
from .norm import (  # noqa: F401
    group_norm,
    layer_norm,
    local_response_norm,
    normalize,
    rms_norm,
)
from .pooling import (  # noqa: F401
    adaptive_avg_pool1d,
    adaptive_max_pool1d,
    avg_pool1d,
    max_pool1d,
    adaptive_avg_pool2d,
    adaptive_avg_pool3d,
    adaptive_max_pool2d,
    avg_pool2d,
    avg_pool3d,
    max_pool2d,
    max_pool3d,
)
from .vision import (  # noqa: F401
    _bilerp,
    grid_sample,
    affine_grid,
    pixel_shuffle,
    pixel_unshuffle,
)
