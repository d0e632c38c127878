from .cyk import cyk_recognize  # noqa: F401
from .hellings import hellings_cfpq  # noqa: F401
