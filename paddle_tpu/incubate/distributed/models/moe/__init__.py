"""paddle.incubate.distributed.models.moe parity (MoELayer + gates).
See moe_layer.py for the TPU-native design notes; dropless.py is the
serving-side layer (no capacity, a stated share of the experts)."""
from .gate import NaiveGate, SwitchGate, GShardGate, BaseGate, build_gate
from .moe_layer import MoELayer, ExpertMLP
from .dropless import DroplessMoELayer, dropless_moe

__all__ = ["MoELayer", "ExpertMLP", "DroplessMoELayer", "dropless_moe", "NaiveGate", "SwitchGate", "GShardGate",
           "BaseGate", "build_gate"]
