"""Text models (ref: zoo/.../models/{textclassification,textmatching})."""

from analytics_zoo_tpu.models.text.classifier import (  # noqa: F401
    TextClassifier,
)
from analytics_zoo_tpu.models.text.knrm import KNRM  # noqa: F401
from analytics_zoo_tpu.models.text.bert_estimators import (  # noqa: F401
    BERTClassifier,
    BERTNER,
)
from analytics_zoo_tpu.models.text.bert_squad import (  # noqa: F401
    BERTSQuAD,
)
from analytics_zoo_tpu.models.text.sparse_decoder_lm import (  # noqa: F401
    ByteDecoderLM,
    LatentDecoderLM,
    SparseDecoderLM,
)
from analytics_zoo_tpu.models.text.looped_decoder_lm import (  # noqa: F401
    LoopedDecoderLM,
)
