from . import persistent  # noqa: F401
