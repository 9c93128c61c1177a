"""repro — LCMP reproduction package."""
