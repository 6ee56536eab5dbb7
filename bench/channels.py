"""Received waveforms of the two channels the configurations name, made in
bulk from the seed in one jitted call per configuration, on the host's CPU
device: the fiber model's FFTs have lengths with large odd factors
(N_i·ℓ_inst·4 = 2^11·915 samples at the HT point), which the TPU compiler
turns into dense DFT products (32 s to compile, 240 MB of scratch), where
the CPU's FFT takes a second.

Copied from the program's channel simulators (`repro.channels.imdd`,
`repro.channels.proakis`), with the parameters read from the
configuration file, so that the benchmark's inputs cannot move with the
program:

  * "imdd" — 40 GBd PAM-2 over an IM/DD fiber link (paper §2.1): RRC
    pulse, MZM at quadrature, chromatic dispersion on the field, ASE on
    the field, square-law photodiode with its bandwidth, receiver AWGN,
    resampled to N_os samples per symbol;
  * "proakis_b" — the magnetic-recording channel (paper §2.2): RC pulse,
    Proakis-B ISI [0.407, 0.815, 0.407] at N_os, AWGN.

Each waveform is normalized to zero mean and unit variance.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

C_LIGHT = 299_792_458.0
PROAKIS_B = (0.407, 0.815, 0.407)


def _rrc(n_taps: int, beta: float, sps: int) -> np.ndarray:
    t = (np.arange(n_taps) - (n_taps - 1) / 2) / sps
    taps = np.zeros_like(t)
    for i, ti in enumerate(t):
        if abs(ti) < 1e-9:
            taps[i] = 1.0 - beta + 4 * beta / np.pi
        elif beta > 0 and abs(abs(ti) - 1 / (4 * beta)) < 1e-9:
            taps[i] = (beta / np.sqrt(2)) * (
                (1 + 2 / np.pi) * np.sin(np.pi / (4 * beta))
                + (1 - 2 / np.pi) * np.cos(np.pi / (4 * beta)))
        else:
            taps[i] = ((np.sin(np.pi * ti * (1 - beta))
                        + 4 * beta * ti * np.cos(np.pi * ti * (1 + beta)))
                       / (np.pi * ti * (1 - (4 * beta * ti) ** 2)))
    return (taps / np.sqrt(np.sum(taps ** 2))).astype(np.float32)


def _rc(n_taps: int, beta: float, sps: int) -> np.ndarray:
    t = (np.arange(n_taps) - (n_taps - 1) / 2) / sps
    den = 1.0 - (2.0 * beta * t) ** 2
    sing = np.abs(den) < 1e-8
    taps = np.where(sing, (np.pi / 4) * np.sinc(1 / (2 * beta)),
                    np.sinc(t) * np.cos(np.pi * beta * t)
                    / np.where(sing, 1.0, den))
    return (taps / np.max(np.abs(taps))).astype(np.float32)


def _upsample(x, sps: int):
    return jnp.zeros((x.shape[0] * sps,), x.dtype).at[::sps].set(x)


def _fir_same(x, taps):
    k = taps.shape[0]
    xp = jnp.pad(x, (k // 2, k - 1 - k // 2))
    return jnp.convolve(xp, taps, mode="valid",
                        precision=jax.lax.Precision.HIGHEST)


def _awgn(key, x, snr_db: float):
    p_noise = jnp.mean(x ** 2) / (10.0 ** (snr_db / 10.0))
    return x + jnp.sqrt(p_noise) * jax.random.normal(key, x.shape, x.dtype)


def _normalize(y):
    return (y - jnp.mean(y)) / (jnp.std(y) + 1e-9)


def _pam2(key, n_syms: int):
    return jax.random.randint(key, (n_syms,), 0, 2).astype(jnp.float32) \
        * 2.0 - 1.0


def _imdd_one(key, n_syms: int, p):
    kbits, knoise, kase = jax.random.split(key, 3)
    x = _upsample(_pam2(kbits, n_syms), p["sim_os"])
    x = _fir_same(x, jnp.asarray(_rrc(p["rrc_taps"], p["rrc_beta"],
                                      p["sim_os"]))) * np.sqrt(p["sim_os"])
    field = jnp.cos(np.pi / 4.0 - p["mzm_vpi_frac"] * (np.pi / 2.0) * x / 2.0)
    fs = p["baud_rate"] * p["sim_os"]
    n = int(field.shape[0])
    f = np.fft.fftfreq(n, d=1.0 / fs)
    d = p["cd_ps_nm_km"] * 1e-12 / 1e-9 / 1e3
    lam = p["wavelength_nm"] * 1e-9
    phase = np.pi * lam ** 2 * d * p["fiber_km"] * 1e3 / C_LIGHT * f ** 2
    field = jnp.fft.ifft(jnp.fft.fft(field.astype(jnp.complex64))
                         * jnp.asarray(np.exp(1j * phase), jnp.complex64))
    p_ase = jnp.mean(jnp.abs(field) ** 2) / (10.0 ** (p["osnr_db"] / 10.0))
    ase = jnp.sqrt(p_ase / 2.0) * (
        jax.random.normal(kase, field.shape)
        + 1j * jax.random.normal(jax.random.fold_in(kase, 1), field.shape))
    current = jnp.abs(field + ase.astype(field.dtype)) ** 2
    lpf = 1.0 / np.sqrt(1.0 + (f / p["pd_bw_hz"]) ** 8)
    current = jnp.real(jnp.fft.ifft(jnp.fft.fft(
        current.astype(jnp.complex64)) * jnp.asarray(lpf, jnp.complex64)))
    current = _awgn(knoise, current.astype(jnp.float32), p["snr_db"])
    return _normalize(current[::p["sim_os"] // p["n_os"]])


def _proakis_one(key, n_syms: int, p):
    kbits, knoise = jax.random.split(key)
    x = _upsample(_pam2(kbits, n_syms), p["n_os"])
    x = _fir_same(x, jnp.asarray(_rc(p["rc_taps"], p["rc_beta"], p["n_os"])))
    h = _upsample(jnp.asarray(PROAKIS_B, jnp.float32), p["n_os"])
    y = _fir_same(x, h[:2 * p["n_os"] + 1])
    return _normalize(_awgn(knoise, y, p["snr_db"]))


_KINDS = {"imdd": _imdd_one, "proakis_b": _proakis_one}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _simulate(key, n_streams: int, n_syms: int, params: tuple):
    p = dict(params)
    one = _KINDS[p["kind"]]
    keys = jax.random.split(key, n_streams)
    return jax.vmap(lambda k: one(k, n_syms, p))(keys)


def waveforms(channel: Dict, seed: int, n_streams: int,
              n_syms: int) -> np.ndarray:
    """(n_streams, n_syms · N_os) float32 waveforms, independent streams."""
    params = tuple(sorted(channel.items()))
    with jax.default_device(jax.devices("cpu")[0]):
        key = jax.random.fold_in(jax.random.PRNGKey(1), seed % (2 ** 31))
        key = jax.random.fold_in(key, seed // (2 ** 31))
        return np.asarray(_simulate(key, n_streams, n_syms, params),
                          np.float32)
