// Native TLS stream for the data socket: OpenSSL's record loop without the
// interpreter.
//
// Python's SSLSocket returns to the interpreter after every TLS record (16 KB
// of plaintext), so a 58 MiB frame is about 3,700 releases and re-takes of
// the interpreter lock; with other Python threads busy each re-take waits for
// one of them. Here one call moves a whole frame: read_exact loops
// SSL_read_ex and write_all loops SSL_write_ex until the frame is done, and
// ctypes releases the lock once around the call.
//
// OpenSSL is not linked: skytls_init dlopens the libssl the process already
// mapped (Python's _ssl's, RTLD_NOLOAD) and resolves what it needs by dlsym,
// so the process keeps one OpenSSL and no headers are needed to build.
//
// The fd stays blocking; SO_RCVTIMEO / SO_SNDTIMEO bound a stalled peer (a
// read or write that times out comes back as SKYTLS_TIMEOUT), so a loop here
// never spins. Errors come back as negative codes; the Python side raises
// what the callers of a Python SSLSocket already catch.

#include <dlfcn.h>
#include <errno.h>
#include <cstddef>
#include <cstdint>
#include <cstdio>
#include <cstring>

namespace {

// return codes (negative); tlsstream.py maps each to an exception
const int64_t SKYTLS_PROTOCOL = -1;  // TLS protocol fault: ssl.SSLError
const int64_t SKYTLS_CLOSED = -2;    // the peer closed: ConnectionError (or b"" where a read may end)
const int64_t SKYTLS_TIMEOUT = -3;   // SO_RCVTIMEO / SO_SNDTIMEO expired: TimeoutError
const int64_t SKYTLS_OS = -4;        // a socket error: OSError(errno)
const int64_t SKYTLS_RETRY = -5;     // EINTR: internal, never returned

// OpenSSL constants (stable ABI values)
const int SSL_ERROR_SSL = 1;
const int SSL_ERROR_WANT_READ = 2;
const int SSL_ERROR_WANT_WRITE = 3;
const int SSL_ERROR_SYSCALL = 5;
const int SSL_ERROR_ZERO_RETURN = 6;
const int SSL_CTRL_SET_MIN_PROTO_VERSION = 123;
const int SSL_CTRL_SET_MAX_PROTO_VERSION = 124;
const int SSL_FILETYPE_PEM = 1;
const int SSL_VERIFY_NONE = 0;
const unsigned long ERR_LIB_SSL = 20;
const unsigned long SSL_R_UNEXPECTED_EOF_WHILE_READING = 294;

struct Api {
    const void* (*TLS_server_method)();
    const void* (*TLS_client_method)();
    void* (*SSL_CTX_new)(const void*);
    void (*SSL_CTX_free)(void*);
    uint64_t (*SSL_CTX_set_options)(void*, uint64_t);
    uint64_t (*SSL_CTX_clear_options)(void*, uint64_t);
    long (*SSL_CTX_ctrl)(void*, int, long, void*);
    int (*SSL_CTX_set_cipher_list)(void*, const char*);
    int (*SSL_CTX_use_certificate_chain_file)(void*, const char*);
    int (*SSL_CTX_use_PrivateKey_file)(void*, const char*, int);
    int (*SSL_CTX_check_private_key)(const void*);
    void (*SSL_CTX_set_verify)(void*, int, void*);
    void* (*SSL_new)(void*);
    void (*SSL_free)(void*);
    int (*SSL_set_fd)(void*, int);
    int (*SSL_accept)(void*);
    int (*SSL_connect)(void*);
    int (*SSL_read_ex)(void*, void*, size_t, size_t*);
    int (*SSL_write_ex)(void*, const void*, size_t, size_t*);
    int (*SSL_get_error)(const void*, int);
    int (*SSL_pending)(const void*);
    const char* (*SSL_get_version)(const void*);
    const void* (*SSL_get_current_cipher)(const void*);
    const char* (*SSL_CIPHER_get_name)(const void*);
    unsigned long (*ERR_peek_last_error)();
    void (*ERR_error_string_n)(unsigned long, char*, size_t);
    void (*ERR_clear_error)();
};

Api api;
bool api_ready = false;

struct Stream {
    void* ssl;
    int sys_errno;   // errno of the last SKYTLS_OS
    uint64_t done;   // bytes moved by the last read_exact / write_all before it failed
    char msg[256];   // OpenSSL's reason for the last SKYTLS_PROTOCOL
};

void error_text(unsigned long err, char* out, size_t n) {
    if (n == 0) return;
    if (err == 0) {
        snprintf(out, n, "TLS error with nothing on OpenSSL's error queue");
        return;
    }
    api.ERR_error_string_n(err, out, n);
}

bool is_unexpected_eof(unsigned long err) {
    // OpenSSL 3 packs lib in bits 23-30 and reason in 0-22 (bit 31 marks a
    // system error); 1.1.1 reports the same event as SSL_ERROR_SYSCALL
    if (err & 0x80000000UL) return false;
    return ((err >> 23) & 0xFFUL) == ERR_LIB_SSL && (err & 0x7FFFFFUL) == SSL_R_UNEXPECTED_EOF_WHILE_READING;
}

// What a failed SSL call means. errno is read before anything else can move it.
int64_t classify(Stream* s, int rc) {
    int saved = errno;
    int e = api.SSL_get_error(s->ssl, rc);
    unsigned long err = api.ERR_peek_last_error();
    switch (e) {
        case SSL_ERROR_ZERO_RETURN:
            return SKYTLS_CLOSED;
        case SSL_ERROR_WANT_READ:
        case SSL_ERROR_WANT_WRITE:
            // a blocking fd wants more only when the kernel timeout expired
            // (EAGAIN) or a signal interrupted the syscall (EINTR)
            return saved == EINTR ? SKYTLS_RETRY : SKYTLS_TIMEOUT;
        case SSL_ERROR_SYSCALL:
            if (err == 0) {
                if (saved == 0) return SKYTLS_CLOSED;  // EOF without close_notify
                if (saved == EINTR) return SKYTLS_RETRY;
                if (saved == EAGAIN || saved == EWOULDBLOCK) return SKYTLS_TIMEOUT;
                s->sys_errno = saved;
                return SKYTLS_OS;
            }
            break;
        default:
            break;
    }
    if (is_unexpected_eof(err)) return SKYTLS_CLOSED;
    error_text(err, s->msg, sizeof(s->msg));
    return SKYTLS_PROTOCOL;
}

template <typename T>
bool resolve(void* lib, const char* name, T* out) {
    *out = reinterpret_cast<T>(dlsym(lib, name));
    return *out != nullptr;
}

}  // namespace

extern "C" {

// Bind to the libssl at `path`, which must already be mapped. 0 on success,
// -1 if it is not mapped, -2 if a function is missing (older than 1.1.1).
int skytls_init(const char* path) {
    if (api_ready) return 0;
    void* lib = dlopen(path, RTLD_NOW | RTLD_NOLOAD);
    if (lib == nullptr) return -1;
    bool ok = true;
#define SKYTLS_RESOLVE(fn) ok = resolve(lib, #fn, &api.fn) && ok
    SKYTLS_RESOLVE(TLS_server_method);
    SKYTLS_RESOLVE(TLS_client_method);
    SKYTLS_RESOLVE(SSL_CTX_new);
    SKYTLS_RESOLVE(SSL_CTX_free);
    SKYTLS_RESOLVE(SSL_CTX_set_options);
    SKYTLS_RESOLVE(SSL_CTX_clear_options);
    SKYTLS_RESOLVE(SSL_CTX_ctrl);
    SKYTLS_RESOLVE(SSL_CTX_set_cipher_list);
    SKYTLS_RESOLVE(SSL_CTX_use_certificate_chain_file);
    SKYTLS_RESOLVE(SSL_CTX_use_PrivateKey_file);
    SKYTLS_RESOLVE(SSL_CTX_check_private_key);
    SKYTLS_RESOLVE(SSL_CTX_set_verify);
    SKYTLS_RESOLVE(SSL_new);
    SKYTLS_RESOLVE(SSL_free);
    SKYTLS_RESOLVE(SSL_set_fd);
    SKYTLS_RESOLVE(SSL_accept);
    SKYTLS_RESOLVE(SSL_connect);
    SKYTLS_RESOLVE(SSL_read_ex);
    SKYTLS_RESOLVE(SSL_write_ex);
    SKYTLS_RESOLVE(SSL_get_error);
    SKYTLS_RESOLVE(SSL_pending);
    SKYTLS_RESOLVE(SSL_get_version);
    SKYTLS_RESOLVE(SSL_get_current_cipher);
    SKYTLS_RESOLVE(SSL_CIPHER_get_name);
    // libcrypto's, found through libssl's dependencies
    SKYTLS_RESOLVE(ERR_peek_last_error);
    SKYTLS_RESOLVE(ERR_error_string_n);
    SKYTLS_RESOLVE(ERR_clear_error);
#undef SKYTLS_RESOLVE
    if (!ok) return -2;
    api_ready = true;
    return 0;
}

// A context with the settings a Python ssl.SSLContext holds: its options,
// protocol bounds (0 = what the library supports), TLS 1.2 cipher list (with
// its @SECLEVEL) and, for a server, the certificate chain and key. A client
// verifies nothing (the receivers' certificates are self-signed). NULL on
// failure, OpenSSL's reason in err.
void* skytls_ctx_new(int server, uint64_t options, int min_version, int max_version, const char* ciphers,
                     const char* certfile, const char* keyfile, char* err, size_t errlen) {
    api.ERR_clear_error();
    void* ctx = api.SSL_CTX_new(server ? api.TLS_server_method() : api.TLS_client_method());
    if (ctx == nullptr) {
        error_text(api.ERR_peek_last_error(), err, errlen);
        return nullptr;
    }
    api.SSL_CTX_clear_options(ctx, ~(uint64_t)0);
    api.SSL_CTX_set_options(ctx, options);
    bool ok = api.SSL_CTX_ctrl(ctx, SSL_CTRL_SET_MIN_PROTO_VERSION, min_version, nullptr) == 1 &&
              api.SSL_CTX_ctrl(ctx, SSL_CTRL_SET_MAX_PROTO_VERSION, max_version, nullptr) == 1 &&
              api.SSL_CTX_set_cipher_list(ctx, ciphers) == 1;
    if (ok && server) {
        ok = api.SSL_CTX_use_certificate_chain_file(ctx, certfile) == 1 &&
             api.SSL_CTX_use_PrivateKey_file(ctx, keyfile, SSL_FILETYPE_PEM) == 1 &&
             api.SSL_CTX_check_private_key(ctx) == 1;
    }
    if (!ok) {
        error_text(api.ERR_peek_last_error(), err, errlen);
        api.SSL_CTX_free(ctx);
        return nullptr;
    }
    if (!server) api.SSL_CTX_set_verify(ctx, SSL_VERIFY_NONE, nullptr);
    return ctx;
}

void skytls_ctx_free(void* ctx) {
    if (ctx != nullptr) api.SSL_CTX_free(ctx);
}

// A stream on a connected, blocking fd, not yet shaken hands. NULL on failure.
void* skytls_new(void* ctx, int fd) {
    void* ssl = api.SSL_new(ctx);
    if (ssl == nullptr) return nullptr;
    if (api.SSL_set_fd(ssl, fd) != 1) {
        api.SSL_free(ssl);
        return nullptr;
    }
    Stream* s = new Stream();
    s->ssl = ssl;
    return s;
}

// The handshake, as the server or the client. 0, or a negative code.
int64_t skytls_handshake(void* sp, int server) {
    Stream* s = static_cast<Stream*>(sp);
    for (;;) {
        api.ERR_clear_error();
        errno = 0;
        int rc = server ? api.SSL_accept(s->ssl) : api.SSL_connect(s->ssl);
        if (rc == 1) return 0;
        int64_t code = classify(s, rc);
        if (code != SKYTLS_RETRY) return code;
    }
}

// One SSL_read_ex: up to n bytes, at least 1. 0 when the peer closed.
int64_t skytls_read(void* sp, uint8_t* buf, uint64_t n) {
    Stream* s = static_cast<Stream*>(sp);
    if (n == 0) return 0;
    for (;;) {
        size_t got = 0;
        api.ERR_clear_error();
        errno = 0;
        if (api.SSL_read_ex(s->ssl, buf, n, &got) == 1) return (int64_t)got;
        int64_t code = classify(s, 0);
        if (code == SKYTLS_CLOSED) return 0;
        if (code != SKYTLS_RETRY) return code;
    }
}

// Exactly n bytes into buf: n, or a negative code with s->done bytes read.
int64_t skytls_read_exact(void* sp, uint8_t* buf, uint64_t n) {
    Stream* s = static_cast<Stream*>(sp);
    uint64_t done = 0;
    while (done < n) {
        size_t got = 0;
        api.ERR_clear_error();
        errno = 0;
        if (api.SSL_read_ex(s->ssl, buf + done, n - done, &got) == 1) {
            done += got;
            continue;
        }
        int64_t code = classify(s, 0);
        if (code == SKYTLS_RETRY) continue;
        s->done = done;
        return code;
    }
    return (int64_t)n;
}

// All n bytes of buf: n, or a negative code with s->done bytes written.
int64_t skytls_write_all(void* sp, const uint8_t* buf, uint64_t n) {
    Stream* s = static_cast<Stream*>(sp);
    uint64_t done = 0;
    while (done < n) {
        size_t put = 0;
        api.ERR_clear_error();
        errno = 0;
        if (api.SSL_write_ex(s->ssl, buf + done, n - done, &put) == 1) {
            done += put;
            continue;
        }
        int64_t code = classify(s, 0);
        if (code == SKYTLS_RETRY) continue;  // OpenSSL asks for the same arguments again
        s->done = done;
        return code;
    }
    return (int64_t)n;
}

// Decrypted bytes buffered in the current record: a read returns them
// without touching the fd, which a readiness wait cannot see.
int skytls_pending(void* sp) {
    return api.SSL_pending(static_cast<Stream*>(sp)->ssl);
}

const char* skytls_version(void* sp) {
    return api.SSL_get_version(static_cast<Stream*>(sp)->ssl);
}

const char* skytls_cipher(void* sp) {
    const void* c = api.SSL_get_current_cipher(static_cast<Stream*>(sp)->ssl);
    return c == nullptr ? "" : api.SSL_CIPHER_get_name(c);
}

const char* skytls_error(void* sp) {
    return static_cast<Stream*>(sp)->msg;
}

int skytls_errno(void* sp) {
    return static_cast<Stream*>(sp)->sys_errno;
}

uint64_t skytls_done(void* sp) {
    return static_cast<Stream*>(sp)->done;
}

// Frees the stream. No close_notify is sent: a Python SSLSocket's close()
// sends none either, and the peer reads the fd's close as the end.
void skytls_free(void* sp) {
    Stream* s = static_cast<Stream*>(sp);
    if (s == nullptr) return;
    api.SSL_free(s->ssl);
    delete s;
}

}  // extern "C"
