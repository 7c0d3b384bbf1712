"""CTR model zoo — the paper's four evaluation models, and MLPerf's
DLRM-DCNv2 (numeric features, multi-hot fields, a low-rank cross)."""

from .common import CTRModel, CTRModelSpec, bce_loss
from .dcn import DCN
from .dcnv2 import DCNv2
from .deepfm import DeepFM
from .dlrm_dcnv2 import DLRMDCNv2
from .widedeep import WideDeep

CTR_MODELS = {
    "dcn": DCN,
    "dcnv2": DCNv2,
    "widedeep": WideDeep,
    "deepfm": DeepFM,
    "dlrm_dcnv2": DLRMDCNv2,
}


def make_ctr_model(name: str, spec: CTRModelSpec) -> CTRModel:
    return CTR_MODELS[name](spec)


__all__ = ["CTRModel", "CTRModelSpec", "CTR_MODELS", "make_ctr_model",
           "DCN", "DCNv2", "WideDeep", "DeepFM", "DLRMDCNv2", "bce_loss"]
