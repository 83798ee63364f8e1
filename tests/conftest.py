"""Test config: run on an 8-device virtual CPU mesh (SURVEY.md §4 —
multi-controller simulation replaces the reference's 2-process NCCL tests).
"""
import os
import sys

os.environ['JAX_PLATFORMS'] = 'cpu'  # tests never touch an accelerator
flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = \
        flags + ' --xla_force_host_platform_device_count=8'
# every run compiles afresh: an XLA:CPU executable loaded from the
# persistent cache differs in the last bits from the same program
# compiled in-process, which breaks the bit-identity tests that compare
# two separately compiled programs (the cache tests re-enable it in
# their own subprocesses)
os.environ.setdefault('JAX_ENABLE_COMPILATION_CACHE', 'false')

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
