"""On-chip benchmark of the PreSto produce path (see ``run.py``)."""
