"""Host-side input pipelines feeding the TPU (the InputMode.TENSORFLOW perf
path).

The reference shipped its input pipeline as example code driving tf.data
(/root/reference/examples/resnet/imagenet_preprocessing.py:259 input_fn,
cifar_preprocessing.py:42 parse_record); here it is a framework subpackage:
TFRecord shards are streamed in chunks through the native C++ reader
(:mod:`tensorflowonspark_tpu.native_io`) with shard read-ahead overlapping
IO against the parse stage, records re-ordered by a bounded shuffle buffer,
images decoded/augmented with PIL+numpy on a thread pool — or, with
``decode_workers > 0``, GIL-free in the :mod:`~tensorflowonspark_tpu.data.
decode_plane` worker processes writing into shared-memory slabs — straight
into preallocated batch buffers, and fixed-shape batches double-buffered
onto the device mesh — static shapes and steady feed keep XLA and the MXU
busy.
"""

from tensorflowonspark_tpu.data.loader import (  # noqa: F401
    ImagePipeline,
    device_prefetch,
    loop_prefetch,
    shard_files,
)
from tensorflowonspark_tpu.data.decode_plane import (  # noqa: F401
    DecodeAutotuner,
    DecodePlane,
)
from tensorflowonspark_tpu.data.text_plane import (  # noqa: F401
    TextPipeline,
    pack_bins,
)
from tensorflowonspark_tpu.data.tokenizer import (  # noqa: F401
    TokenizeError,
    Tokenizer,
)
from tensorflowonspark_tpu.data import cifar, imagenet  # noqa: F401
