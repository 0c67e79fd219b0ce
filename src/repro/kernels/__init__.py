from .ops import attention_op, ssd_scan_op
from .ref import ref_attention, ref_ssd
