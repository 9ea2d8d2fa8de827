"""Model zoo: shared layers + the 10 assigned LM architectures + CapsNet."""
from repro.models import capsnet, layers, lm, matrix_capsnet, moe, ssm
from repro.models.lm import ArchConfig

__all__ = ["capsnet", "layers", "lm", "matrix_capsnet", "moe", "ssm",
           "ArchConfig"]
