"""Clusters built otherwise than ``benchmark/cluster.py`` builds them.

A configuration file may name a module of this directory under ``cluster``
(``benchmark/plugins.py`` finds it).  The module has

- ``Cluster``: as a rule a subclass of ``benchmark.cluster.Cluster`` that
  overrides one of ``start``'s steps (``endpoints``, ``tick_options``,
  ``store_options``, ``pd_client``, ``make_client``) or a method the harness
  calls (``counters``, ``tick_probe``, ``read_all``, ``replica_values``); the
  configuration's ``options`` object reaches it unread as ``self.options``;
- ``IMPLEMENTS``: which values of the configuration's guarded fields
  (``plugins.CONFIG_GUARDED``) it builds, for a start
  ``dict(benchmark.cluster.IMPLEMENTS, stores=[3, 5])``; any other value is
  refused by name.
"""
