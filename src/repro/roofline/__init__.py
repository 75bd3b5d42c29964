from .advisor import Advice, advise_allreduce, analytic_time
from .hw import V5E, HwSpec

__all__ = ["Advice", "advise_allreduce", "analytic_time", "V5E", "HwSpec"]
