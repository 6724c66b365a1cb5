"""Repo tooling (lint). A package so ``tools.graft_lint`` imports cleanly
once the repo root is on ``sys.path``."""
