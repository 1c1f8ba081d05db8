"""Operation and byte counts computed from shapes alone.

These are the numerators of every utilisation and roofline share the
benchmark reports; they live here so that no change to the program can
move them.
"""
