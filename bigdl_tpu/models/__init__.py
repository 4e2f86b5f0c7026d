"""bigdl_tpu.models — reference workloads (reference ``$B/models/``)."""

from bigdl_tpu.models import lenet
from bigdl_tpu.models import vgg
from bigdl_tpu.models import resnet
from bigdl_tpu.models import inception
from bigdl_tpu.models import autoencoder
from bigdl_tpu.models import rnn
from bigdl_tpu.models import transformer
from bigdl_tpu.models import hybrid
from bigdl_tpu.models import vit
from bigdl_tpu.models.generation import generate, generate_speculative
from bigdl_tpu.models.lm_server import LMServer, make_http_server
from bigdl_tpu.models.serving import ContinuousLMServer
