"""Model zoo built on the paddle_tpu static-graph API.

Parity targets (BASELINE.json `configs`): LeNet/MNIST, ResNet-50, BERT/ERNIE,
DeepFM CTR, Transformer NMT; plus the book-suite families (word2vec,
sentiment conv/stacked-LSTM, VGG16 — reference ``tests/book/``); and
``qwen3_next``, a decoder-only hybrid (Gated DeltaNet / gated GQA attention
layers, each with a sparse expert layer) trained as one expert-parallel
rank's share.
"""

from . import (  # noqa: F401
    bert,
    deepfm,
    lenet,
    qwen3_next,
    recommender,
    resnet,
    sentiment,
    seq2seq,
    tagger,
    transformer,
    vgg,
    word2vec,
)
