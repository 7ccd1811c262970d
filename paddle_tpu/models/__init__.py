"""Model zoo covering the five BASELINE configs (BASELINE.md):
MNIST MLP, ResNet-50, BERT-base pretrain, DeepFM CTR, Transformer NMT;
and ``decoder_lm``, the config-driven decoder builder (OLMoE-1B-7B)."""
from . import mnist      # noqa: F401
from . import resnet     # noqa: F401
from . import bert       # noqa: F401
from . import deepfm     # noqa: F401
from . import transformer  # noqa: F401
from . import vgg        # noqa: F401
from . import yolov3     # noqa: F401
from . import faster_rcnn  # noqa: F401
from . import mask_rcnn   # noqa: F401
from . import retinanet   # noqa: F401
from . import decoder_lm  # noqa: F401
