"""Model zoo built on the paddle_tpu static-graph API.

Parity targets (BASELINE.json `configs`): LeNet/MNIST, ResNet-50, BERT/ERNIE,
DeepFM CTR, Transformer NMT; plus the book-suite families (word2vec,
sentiment conv/stacked-LSTM, VGG16 — reference ``tests/book/``).
"""

from . import (  # noqa: F401
    bert,
    deepfm,
    lenet,
    recommender,
    resnet,
    sentiment,
    seq2seq,
    tagger,
    transformer,
    vgg,
    word2vec,
)
