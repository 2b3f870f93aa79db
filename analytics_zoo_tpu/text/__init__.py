from analytics_zoo_tpu.text.bert import (
    BertConfig, BertModule, TransformerModule,
)
from analytics_zoo_tpu.text.hybrid_decoder import (
    HybridDecoder, HybridDecoderConfig,
)
from analytics_zoo_tpu.text.estimators import (
    BERTClassifier, BERTNER, BERTSQuAD,
)

__all__ = ["BertConfig", "BertModule", "TransformerModule",
           "HybridDecoder", "HybridDecoderConfig",
           "BERTClassifier", "BERTNER", "BERTSQuAD"]
