"""File-backed data: tokenizer, token shards, array datasets.

The synthetic streams (core/data.py) keep benchmarks hermetic; this package
is the real-data path the BASELINE configs name (MNIST/ImageNet-style array
files, LM token shards): a trainable byte-level BPE tokenizer with no
external downloads, a corpus encoder CLI, and memory-mapped datasets that
shard by data-parallel rank and checkpoint their cursor.
"""

from easydl_tpu.data.clicks import (  # noqa: F401
    ClickLogDataset,
    encode_click_tsv,
)
from easydl_tpu.data.datasets import (  # noqa: F401
    ArrayImageDataset,
    TokenFileDataset,
    write_token_shards,
)
from easydl_tpu.data.images import (  # noqa: F401
    convert_mnist,
    import_image_folder,
    read_idx,
)
from easydl_tpu.data.source import open_dataset, restore_cursor  # noqa: F401
from easydl_tpu.data.tokenizer import ByteBpeTokenizer  # noqa: F401
