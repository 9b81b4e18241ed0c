"""Launchers: production mesh, multi-pod dry-run, train/serve drivers, and
the four LIKWID-analogue CLIs (topology / pin / perfctr / features).

``repro.launch.dryrun.force_host_devices`` gives the CPU backend the dry
run's 512 devices; only the dry-run entry points call it.
"""
