"""Model zoo matching the reference's benchmark/book models
(BASELINE.json configs + the benchmark/README anchors): MNIST conv,
ResNet-50 (+SE-ResNeXt), VGG-16, AlexNet, GoogLeNet, stacked-LSTM
language model, Transformer NMT, DeepFM CTR, SSD detector — and, past
the reference's time, a latent-attention sparse-expert decoder LM.
"""
from . import alexnet  # noqa: F401
from . import decoder_moe  # noqa: F401
from . import deepfm  # noqa: F401
from . import googlenet  # noqa: F401
from . import lstm_lm  # noqa: F401
from . import mnist  # noqa: F401
from . import resnet  # noqa: F401
from . import ssd  # noqa: F401
from . import transformer  # noqa: F401
from . import vgg  # noqa: F401
