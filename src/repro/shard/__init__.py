from .mesh import explicit_axes, make_mesh  # noqa: F401
from .plans import MeshPlan  # noqa: F401
