"""Distributed execution layer: sharding rules, the sharded Algorithm 3.1
matvec, and int8 error-feedback gradient compression.

Modules
-------
``sharding``
    Named-sharding placement rules (FSDP over the ``("pod", "data")`` axes,
    tensor parallelism over ``"model"``) consumed by ``launch/steps.py``.
``fastsum_dist``
    ``shard_map``-based distributed NFFT fast summation: the node dimension
    is sharded; the spectral accumulation is either one psum of the
    half-spectrum support block per matvec (``spectral_mode="psum"``) or a
    reduce-scattered pencil-decomposed FFT (``"pencil"``) whose per-device
    spectrum memory, FFT flops, and collective payload scale ~1/P.
``pencil_fft``
    The distributed ``rfftn``/``irfftn`` pair behind the pencil mode: grid
    axes 0 (and 1, d >= 3) sharded over row x col mesh-axis groups, local
    trailing-axis FFTs + one ``all_to_all`` transpose per sharded axis.
``compression``
    Block-wise int8 quantization with error feedback for gradient
    all-reduce (``compress_psum``) and per-step compression in the train
    loop (``apply_error_feedback``).
"""

from repro.dist.compression import (
    BLOCK, CompressionState, apply_error_feedback, compress_decompress,
    compress_psum, init_compression_state)
from repro.dist.fastsum_dist import (
    SPECTRAL_MODES, distributed_matvec_fn, make_sharded_matvec,
    resolve_pencil_spec)
from repro.dist.pencil_fft import (
    PencilSpec, make_pencil_spec, pencil_irfftn, pencil_rfftn)
from repro.dist.sharding import (
    FSDP_AXES, MODEL_AXIS, batch_specs, cache_specs, named, param_specs)

__all__ = [
    "BLOCK", "CompressionState", "FSDP_AXES", "MODEL_AXIS", "PencilSpec",
    "SPECTRAL_MODES", "apply_error_feedback", "batch_specs", "cache_specs",
    "compress_decompress", "compress_psum", "distributed_matvec_fn",
    "init_compression_state", "make_pencil_spec", "make_sharded_matvec",
    "named", "param_specs", "pencil_irfftn", "pencil_rfftn",
    "resolve_pencil_spec",
]
